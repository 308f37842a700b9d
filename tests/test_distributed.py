"""Multi-host distributed backend (parallel/distributed.py): a REAL
two-process JAX distributed system on CPU — collectives cross process
boundaries over Gloo (the test stand-in for DCN between TPU hosts), the
global mesh packs tp inside each host, and sharded train steps produce
identical replicated losses on every host.

The reference's distributed story is single-node OTP messaging
(SURVEY.md §2.9); multi-host model execution is a new capability with no
reference counterpart, so these tests are the contract.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "distributed_worker.py")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_mesh_trains_identically(tmp_path):
    port = free_port()
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=REPO)
    # stdout/stderr go to FILES: piping both workers and draining them
    # sequentially can deadlock — an undrained worker blocks on a full
    # pipe, stops participating in the collectives, and the OTHER worker
    # stalls, surfacing as a misleading timeout
    files = []
    procs = []
    for pid in range(2):
        fo = open(tmp_path / f"w{pid}.out", "w+")
        fe = open(tmp_path / f"w{pid}.err", "w+")
        files.append((fo, fe))
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, str(port), str(pid)],
            env=env, stdout=fo, stderr=fe, text=True))
    outs = []
    for p, (fo, fe) in zip(procs, files):
        try:
            p.wait(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed worker timed out")
        fo.seek(0)
        fe.seek(0)
        out, err = fo.read(), fe.read()
        fo.close()
        fe.close()
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    by_pid = {o["pid"]: o["losses"] for o in outs}
    assert set(by_pid) == {0, 1}
    # the loss is replicated via the dp grad psum that crossed processes:
    # both hosts must see the same values, and training must move them
    assert by_pid[0] == by_pid[1]
    assert by_pid[0][1] < by_pid[0][0]


def test_process_id_alone_is_rejected():
    from quoracle_tpu.parallel.distributed import init_process
    with pytest.raises(ValueError, match="process_id given without"):
        init_process(process_id=1)


def test_single_process_helpers_degrade():
    """init_process with no cluster env, multihost_mesh, host_local_batch,
    and barrier must all work in a plain single-process run."""
    import jax
    from jax.sharding import PartitionSpec as P

    from quoracle_tpu.parallel.distributed import (
        barrier, host_local_batch, init_process, multihost_mesh,
    )
    info = init_process()
    assert info.num_processes >= 1
    assert info.local_devices == jax.local_device_count()
    tp = 2 if jax.local_device_count() % 2 == 0 else 1
    mesh = multihost_mesh(tp=tp)
    assert int(np.prod(list(mesh.shape.values()))) == jax.device_count()
    x = np.arange(mesh.shape["dp"] * 3, dtype=np.float32).reshape(-1, 3)
    g = host_local_batch(x, mesh, P("dp", None))
    assert g.shape == x.shape
    barrier("t")


class _FakeDev:
    def __init__(self, process_index):
        self.process_index = process_index


def test_multihost_mesh_rejects_cross_host_tp():
    """A synthetic 2-host × 4-device list: host membership comes from each
    device's process_index, so a tp wider than one host's devices is
    rejected even when it divides the GLOBAL count — the exact silent
    cross-DCN-psum hazard the host packing exists to prevent."""
    from quoracle_tpu.parallel.distributed import _hosts_of, multihost_mesh
    devs = [_FakeDev(p) for p in (0, 0, 0, 0, 1, 1, 1, 1)]
    assert [len(g) for g in _hosts_of(devs)] == [4, 4]
    # ValueError, not AssertionError: these contracts must hold under -O too
    with pytest.raises(ValueError, match="ICI"):
        multihost_mesh(tp=8, devices=devs)       # divides global, spans DCN
    # uneven host populations are a layout bug, not a reshape surprise
    with pytest.raises(ValueError, match="uneven"):
        _hosts_of([_FakeDev(0), _FakeDev(0), _FakeDev(1)])
