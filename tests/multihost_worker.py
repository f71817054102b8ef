"""Worker process for the 2-process jax.distributed test (VERDICT r3
item 6; PR 7 re-pointed it at the meshrun subsystem).  Launched by
tests/test_parallel.py with env:

  WTF_COORD   coordinator address (localhost:port)
  WTF_NPROC   number of processes
  WTF_PID     this process's id
  (JAX_PLATFORMS=cpu and xla_force_host_platform_device_count are set by
  the parent so each process contributes 4 virtual CPU devices)

Joins the distributed runtime via init_multihost, runs one shard_map
mesh chunk (wtf_tpu/meshrun/executor.py) over the global 8-device mesh —
the same executor MeshRunner dispatches — and reads back the merged
coverage bitmap its in-graph boolean all-reduce produced (DCN-analog
collective).  Prints one JSON line whose coverage digest the parent
compares across both processes.
"""

import json
import os
import sys


def main() -> None:
    import numpy as np

    from wtf_tpu.harness import demo_tlv
    from wtf_tpu.interp.runner import Runner, warm_decode_cache
    from wtf_tpu.meshrun import (
        init_multihost, make_mesh_chunk, replicate, shard_machine,
    )

    mesh = init_multihost(coordinator=os.environ["WTF_COORD"],
                          num_processes=int(os.environ["WTF_NPROC"]),
                          process_id=int(os.environ["WTF_PID"]))
    import jax
    import jax.numpy as jnp

    n_devices = len(jax.devices())
    assert n_devices == mesh.size, (n_devices, mesh.size)

    payload = b"\x01\x02AB\x03\x08CCCCCCCC"
    n_lanes = 2 * n_devices
    snapshot = demo_tlv.build_snapshot()
    runner = Runner(snapshot, n_lanes=n_lanes, uop_capacity=1 << 10,
                    overlay_slots=8, edge_bits=12, chunk_steps=8)
    warm_decode_cache(runner, demo_tlv.TARGET, payload, limit=4096)
    view = runner.view()
    for lane in range(n_lanes):
        view.virt_write(lane, demo_tlv.INPUT_GVA, payload)
        view.r["gpr"][lane, 2] = np.uint64(len(payload))
    runner.push(view)

    machine = shard_machine(runner.machine, mesh)
    tab = replicate(runner.cache.device(), mesh)
    image = replicate(runner.physmem.image, mesh)
    # the mesh chunk's in-graph coverage all-reduce IS the cross-process
    # collective under test: its output is replicated on every host
    machine, cov, _edge, _steps = make_mesh_chunk(8, mesh, donate=False)(
        tab, image, machine, jnp.uint64(500))

    from jax.experimental import multihost_utils

    cov_local = np.asarray(cov.addressable_shards[0].data)
    icount = np.asarray(
        multihost_utils.process_allgather(machine.icount, tiled=True))
    assert icount.shape[0] == n_lanes, icount.shape
    print(json.dumps({
        "pid": int(os.environ["WTF_PID"]),
        "devices": n_devices,
        "lanes": n_lanes,
        "instructions": int(icount.sum()),
        "min_lane_icount": int(icount.min()),
        "cov_words_set": int((cov_local != 0).sum()),
        "cov_digest": hex(int(np.bitwise_xor.reduce(
            cov_local.astype(np.uint64) * np.arange(1, len(cov_local) + 1,
                                                    dtype=np.uint64)))),
    }))


if __name__ == "__main__":
    sys.exit(main())
