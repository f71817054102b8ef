"""meshrun: a jax.sharding.Mesh over the lane axis as one logical backend.

The reference wtf scales by one fuzzer process per core aggregating
coverage over TCP (SURVEY.md §2.7); the TPU-native answer makes
lanes-per-chip x chips the headline axis.  Machine state is SoA with a
leading lane axis, so the whole campaign loop shards as data
parallelism:

  mesh.py      mesh construction + pytree placement (lanes split,
               image/uop-table replicated, multi-host init)
  reduce.py    the ONE shard-aware coverage OR-reduce family (chunk
               bitmaps, batch merge with reference set-union credit)
  executor.py  the shard_map chunk executor — the compiled chunk
               carries exactly one cross-device collective, the
               coverage all-reduce (pinned statically by `wtf-tpu
               lint`'s mesh family)
  runner.py    MeshRunner: the host servicing loop over a sharded batch
  backend.py   MeshBackend: the one-logical-backend seam the fuzz loop,
               dist clients and CLI drive (`campaign --mesh-devices N`)

Imports resolve lazily (PEP 562) so `wtf_tpu.backend` can pull the
shared coverage merge without importing the runner stack.
"""

from __future__ import annotations

_EXPORTS = {
    "LANE_AXIS": "mesh",
    "make_mesh": "mesh",
    "init_multihost": "mesh",
    "lane_sharding": "mesh",
    "replicated_sharding": "mesh",
    "shard_machine": "mesh",
    "replicate": "mesh",
    "or_reduce_lanes": "reduce",
    "merged_coverage": "reduce",
    "merge_coverage": "reduce",
    "make_mesh_merge": "reduce",
    "make_mesh_chunk": "executor",
    "MeshRunner": "runner",
    "MeshBackend": "backend",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f"{__name__}.{module}"), name)
