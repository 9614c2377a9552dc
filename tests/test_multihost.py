"""True multi-process distributed test (SURVEY.md §4.5 item 3: "keep a small
subprocess suite for true multi-host (jax.distributed over localhost) to
cover DCN init, launch CLI").

Two REAL processes rendezvous through jax.distributed's coordination service
(launched by our CLI with the PADDLE_* env contract) and run a cross-host
psum — the reference's test_dist_base.py pattern, NCCL replaced by the
coordination service + XLA CPU collectives.
"""
import os
import subprocess
import sys
import textwrap

import pytest

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)  # 1 device per process (true multi-host)
    sys.path.insert(0, "__REPO__")
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()   # jax.distributed.initialize under the hood
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == 2, jax.process_count()
    assert dist.get_world_size() == 2
    devs = jax.devices()
    mesh = Mesh(devs, ("dp",))
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")),
        np.full((1, 4), 1.0 + jax.process_index()))

    def f(x):
        return jax.lax.psum(x, "dp")

    from paddle_tpu.distributed.jax_compat import shard_map as compat_shard_map

    g = compat_shard_map(f, mesh, in_specs=P("dp"), out_specs=P("dp"),
                         axis_names={"dp"})
    out = jax.jit(g)(arr)
    local = np.asarray(out.addressable_shards[0].data)
    # psum of per-process values 1.0 and 2.0 over both hosts
    assert np.allclose(local, 3.0), local
    print("MULTIHOST_OK", jax.process_index(), flush=True)
""")


SUBGROUP_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)  # 1 device per process
    sys.path.insert(0, "__REPO__")
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = dist.get_rank()
    assert dist.get_world_size() == 3

    # --- subgroup collective: ONLY ranks {0, 1} call it. If the op secretly
    # needed all processes (the round-1 host-gather design), it would hang
    # waiting for rank 2 and the launch would time out.
    g01 = dist.new_group([0, 1])
    if rank in (0, 1):
        t = paddle.to_tensor(np.full((4,), 1.0 + rank, np.float32))
        dist.all_reduce(t, group=g01)
        assert np.allclose(np.asarray(t.numpy()), 3.0), t
        b = paddle.to_tensor(np.full((2,), rank * 10.0, np.float32))
        dist.broadcast(b, src=1, group=g01)
        assert np.allclose(np.asarray(b.numpy()), 10.0), b

    # --- pairwise p2p between 0 and 2; rank 1 does not participate
    if rank == 0:
        dist.send(paddle.to_tensor(np.arange(4.0, dtype=np.float32)), dst=2)
    elif rank == 2:
        out = paddle.to_tensor(np.zeros(4, np.float32))
        dist.recv(out, src=0)
        assert np.allclose(np.asarray(out.numpy()),
                           np.arange(4.0, dtype=np.float32)), out

    dist.barrier()
    print("SUBGROUP_OK", rank, flush=True)
""")


def _launch(tmp_path, script_text, nproc):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(script_text.replace("__REPO__", repo))
    # workers stay off any chip; the launcher refuses several local
    # workers unless told they are CPU-only
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    log_dir = tmp_path / "log"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", str(nproc), "--log_dir", str(log_dir),
         str(script)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=220,
    )
    logs = ""
    for i in range(nproc):
        p = log_dir / f"workerlog.{i}"
        if p.exists():
            logs += f"--- worker {i}\n" + p.read_text()[-2000:]
    return r, logs


@pytest.mark.timeout(240)
def test_two_process_dcn_bootstrap_and_psum(tmp_path):
    r, logs = _launch(tmp_path, WORKER, 2)
    assert r.returncode == 0, f"launch failed\n{r.stderr[-2000:]}\n{logs}"
    assert "MULTIHOST_OK 0" in logs and "MULTIHOST_OK 1" in logs, logs


@pytest.mark.timeout(240)
def test_subgroup_collectives_exclude_nonmembers(tmp_path):
    """VERDICT r1 #7: a 2-rank subgroup op must complete with rank 2 never
    participating, and p2p send/recv only involves the pair."""
    r, logs = _launch(tmp_path, SUBGROUP_WORKER, 3)
    assert r.returncode == 0, f"launch failed\n{r.stderr[-2000:]}\n{logs}"
    for i in range(3):
        assert f"SUBGROUP_OK {i}" in logs, logs
