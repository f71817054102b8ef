"""Self-healing device runtime (supervise/).

  supervisor.Supervisor  the per-backend dispatch guard: watchdog,
                         batch-boundary snapshot + rebuild/replay
                         recovery, per-batch integrity + quarantine
  ladder.DegradationLadder
                         megachunk -> batch -> fixed-chunk
                         step-down with hysteresis re-promotion
  integrity              the jitted invariant/digest fold and the
                         poison/mask write-side helpers

See supervisor.py's module docstring for the full contract; SEAM_SITES
is the lint-pinned enumeration of every dispatch entry point that must
route through Supervisor.dispatch.
"""

from wtf_tpu.supervise.ladder import DegradationLadder  # noqa: F401
from wtf_tpu.supervise.supervisor import (  # noqa: F401
    DEVICE_ERROR, DEVICE_HANG, DEVICE_POISON, MACHINE_SEAMS, SEAM_SITES,
    SUPERVISED_SEAMS, DispatchError, DispatchFailure, DispatchHang,
    LanePoisoned, Supervisor,
)
