"""Training-resilience chaos suite (ISSUE 7).

Proves the tentpole contract end to end: no fault point can leave a
checkpoint directory that ``load_state_dict`` reads as complete-but-
corrupt, and a training run killed at a faultinject-chosen step resumes
from ``latest`` with bit-identical params and loss trajectory versus an
uninterrupted run — in-process (``preempt-signal``), under a REAL
SIGTERM in a subprocess, and (multihost-marked) across 2 processes.
Plus: divergence rollback, bounded step retry, async-handle failure
semantics, retention/manifest/GC, and Prometheus visibility.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as popt
from paddle_tpu.distributed import (
    CheckpointManager,
    TrainingPreempted,
    load_state_dict,
    pack_train_state,
    save_state_dict,
    unpack_train_state,
)
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.framework import random as prandom
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.hapi.model import Model
from paddle_tpu.io import Dataset
from paddle_tpu.testing.faultinject import FaultPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- helpers

class _ToyData(Dataset):
    def __init__(self, n=16, d=8, seed=3):
        rng = np.random.default_rng(seed)
        self.x = rng.standard_normal((n, d)).astype(np.float32)
        self.y = rng.standard_normal((n, 1)).astype(np.float32)

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def _build_model(seed=7, lr=0.05):
    prandom.seed(seed)
    np.random.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    m = Model(net)
    m.prepare(optimizer=popt.Momentum(learning_rate=lr, momentum=0.9,
                                      parameters=net.parameters()),
              loss=nn.MSELoss())
    return m


class _LossRec(Callback):
    def __init__(self, sink):
        self.sink = sink

    def on_train_batch_end(self, step, logs=None):
        self.sink.append(float(logs["loss"]))


def _params(model):
    return {k: np.asarray(v._data)
            for k, v in model.network.state_dict().items()}


# ---------------------------------------------------- atomic commit layer

class TestAtomicCommit:
    def test_io_error_never_leaves_torn_committed_dir(self, tmp_path):
        """ckpt-io-error at EVERY file-write offset: the failed save must
        leave only staging wreckage; the previous committed checkpoint
        stays loadable and `latest` never moves to a torn dir."""
        root = str(tmp_path / "root")
        good = CheckpointManager(root, keep_last_n=5)
        good.save(1, {"w": jnp.full((4, 4), 1.0), "b": jnp.zeros((4,)),
                      "meta": 7})
        # one fault check per data-file write plus one for the marker
        n_checks = len([f for f in os.listdir(good.step_path(1))
                        if f.endswith(".npy")]) + 1
        for at in range(1, n_checks + 1):
            mgr = CheckpointManager(
                root, keep_last_n=5,
                fault_plan=FaultPlan(f"ckpt-io-error:at={at}"))
            with pytest.raises(OSError):
                mgr.save(2, {"w": jnp.full((4, 4), 2.0),
                             "b": jnp.ones((4,)), "meta": 8})
            assert mgr.all_steps() == [1]
            assert mgr.latest_step() == 1
            out = load_state_dict(ckpt.step_dir(root, 1))
            np.testing.assert_array_equal(np.asarray(out["w"]), 1.0)
            assert out["meta"] == 7
        # an at= beyond the write count fires nothing and commits fine
        mgr = CheckpointManager(
            root, keep_last_n=5,
            fault_plan=FaultPlan(f"ckpt-io-error:at={n_checks + 50}"))
        mgr.save(2, {"w": jnp.full((4, 4), 2.0), "b": jnp.ones((4,)),
                     "meta": 8})
        assert mgr.latest_step() == 2

    def test_final_path_appears_atomically(self, tmp_path):
        """The final dir either doesn't exist or is complete — there is
        no observable window where it exists with missing markers."""
        path = str(tmp_path / "ck")
        save_state_dict({"w": jnp.ones((4,))}, path)
        assert ckpt.is_complete(path)
        # staging residue never lingers after a successful commit
        assert [e for e in os.listdir(tmp_path)
                if e.startswith(ckpt.STAGE_PREFIX)] == []

    def test_incomplete_dir_is_invisible_and_unloadable(self, tmp_path):
        """A hand-torn dir (data without markers, or fewer markers than
        process_count) is excluded from discovery AND refused by load."""
        root = str(tmp_path)
        torn = os.path.join(root, "step-5")
        os.makedirs(torn)
        np.save(os.path.join(torn, "w.p0.c0.npy"), np.ones(3))
        assert ckpt.list_steps(root) == []
        assert ckpt.latest_step(root) is None
        with pytest.raises(FileNotFoundError):
            load_state_dict(torn)
        # marker present but claiming 2 processes: still incomplete
        with open(os.path.join(torn, "metadata.p0.json"), "w") as f:
            json.dump({"process_count": 2, "tensors": {}, "objects": {}},
                      f)
        assert not ckpt.is_complete(torn)
        assert ckpt.list_steps(root) == []
        with pytest.raises(FileNotFoundError, match="incomplete"):
            load_state_dict(torn)

    def test_orphaned_staging_gc(self, tmp_path):
        root = str(tmp_path)
        orphan = os.path.join(root, f"{ckpt.STAGE_PREFIX}deadbeef")
        os.makedirs(orphan)
        with open(os.path.join(orphan, "w.npy"), "wb") as f:
            f.write(b"torn")
        CheckpointManager(root)  # init-time GC
        assert not os.path.exists(orphan)

    def test_retention_and_manifest(self, tmp_path):
        root = str(tmp_path)
        mgr = CheckpointManager(root, keep_last_n=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, {"w": jnp.full((2,), float(s))})
        assert mgr.all_steps() == [3, 4]
        man = ckpt.read_manifest(root)
        assert man["steps"] == [3, 4] and man["latest"] == 4
        step, state = mgr.restore()
        assert step == 4
        np.testing.assert_array_equal(np.asarray(state["w"]), 4.0)

    def test_slow_ckpt_write_point(self, tmp_path):
        mgr = CheckpointManager(
            str(tmp_path), fault_plan=FaultPlan(
                "slow-ckpt-write:delay_ms=60,times=1"))
        t0 = time.perf_counter()
        mgr.save(1, {"w": jnp.ones((2,))})
        assert time.perf_counter() - t0 >= 0.05
        assert mgr.latest_step() == 1


# --------------------------------------------------------- async handles

class TestAsyncHandles:
    def test_wait_reraises_every_time(self, tmp_path):
        h = save_state_dict({"w": jnp.ones(2)}, str(tmp_path / "ck"),
                            async_save=True,
                            fault_plan=FaultPlan("ckpt-io-error:at=1"))
        for _ in range(2):  # sticky: not swallowed after the first raise
            with pytest.raises(RuntimeError, match="async checkpoint"):
                h.wait()
        assert h.done and h.failed and not h.succeeded
        assert isinstance(h.exception(), OSError)

    def test_success_handle_flags(self, tmp_path):
        h = save_state_dict({"w": jnp.ones(2)}, str(tmp_path / "ck"),
                            async_save=True)
        h.wait()
        assert h.done and h.succeeded and not h.failed
        assert h.exception() is None

    def test_checkpointer_serializes_and_reraises(self, tmp_path):
        ck2 = ckpt.AsyncCheckpointer()
        # failed in-flight write surfaces on the NEXT save, not silently
        ck2.save({"w": jnp.ones(2)}, str(tmp_path / "a"),
                 fault_plan=FaultPlan("ckpt-io-error:at=1"))
        with pytest.raises(RuntimeError, match="async checkpoint"):
            ck2.save({"w": jnp.ones(2)}, str(tmp_path / "b"))
        # and the manager is usable again afterwards
        ck2.save({"w": jnp.full((2,), 5.0)}, str(tmp_path / "c")).wait()
        out = load_state_dict(str(tmp_path / "c"))
        np.testing.assert_array_equal(np.asarray(out["w"]), 5.0)

    def test_inflight_saves_do_not_interleave(self, tmp_path):
        """A second save while one is slow-writing blocks until the first
        commit lands (single-writer ordering)."""
        mgr = CheckpointManager(
            str(tmp_path), async_save=True,
            fault_plan=FaultPlan("slow-ckpt-write:delay_ms=40,times=1"))
        mgr.save(1, {"w": jnp.full((2,), 1.0)})
        mgr.save(2, {"w": jnp.full((2,), 2.0)})  # joins step-1 first
        mgr.wait()
        assert mgr.all_steps() == [1, 2]
        for s in (1, 2):
            out = load_state_dict(mgr.step_path(s))
            np.testing.assert_array_equal(np.asarray(out["w"]), float(s))


# ------------------------------------------------------------ exact resume

class TestExactResume:
    def test_preempt_at_chosen_step_resumes_bit_identical(self, tmp_path):
        """Kill at a faultinject-chosen step (mid-epoch), resume='auto':
        stitched loss trajectory and final params equal the uninterrupted
        run EXACTLY (zero-tolerance comparison)."""
        data = _ToyData()
        kill_at = int(np.random.default_rng(11).integers(2, 7))

        clean_losses = []
        ma = _build_model()
        ma.fit(data, batch_size=4, epochs=2, shuffle=True, verbose=0,
               callbacks=[_LossRec(clean_losses)],
               ckpt_dir=str(tmp_path / "a"), ckpt_freq=2)
        pa = _params(ma)

        stitched = []
        mb = _build_model()
        with pytest.raises(TrainingPreempted) as ei:
            mb.fit(data, batch_size=4, epochs=2, shuffle=True, verbose=0,
                   callbacks=[_LossRec(stitched)],
                   ckpt_dir=str(tmp_path / "b"), ckpt_freq=2,
                   fault_plan=f"preempt-signal:at={kill_at}")
        assert ei.value.step == kill_at
        assert ei.value.checkpoint_path is not None
        assert ckpt.is_complete(ei.value.checkpoint_path)

        # a DIFFERENTLY-seeded model: restore must overwrite everything
        mc = _build_model(seed=99)
        mc.fit(data, batch_size=4, epochs=2, shuffle=True, verbose=0,
               callbacks=[_LossRec(stitched)],
               ckpt_dir=str(tmp_path / "b"), ckpt_freq=2, resume="auto")
        pc = _params(mc)

        assert stitched == clean_losses
        for k in pa:
            np.testing.assert_array_equal(pa[k], pc[k]), k

    def test_resume_after_ckpt_io_error_kill(self, tmp_path):
        """Run killed by a checkpoint I/O fault mid-epoch: the torn save
        raises out of fit, but `latest` still points at the last good
        commit and resume from it is exact."""
        data = _ToyData()
        clean_losses = []
        ma = _build_model()
        ma.fit(data, batch_size=4, epochs=2, shuffle=True, verbose=0,
               callbacks=[_LossRec(clean_losses)],
               ckpt_dir=str(tmp_path / "a"), ckpt_freq=2)
        pa = _params(ma)

        # kill the SECOND periodic save mid-write: count the files one
        # committed checkpoint holds (checks are per file write + one for
        # the marker), then aim 2 writes into save #2
        mgr_a = CheckpointManager(str(tmp_path / "a"))
        files = os.listdir(mgr_a.step_path(mgr_a.latest_step()))
        checks_per_save = len([f for f in files if f.endswith(".npy")]) + 1
        stitched = []
        mb = _build_model()
        with pytest.raises(OSError):
            mb.fit(data, batch_size=4, epochs=2, shuffle=True, verbose=0,
                   callbacks=[_LossRec(stitched)],
                   ckpt_dir=str(tmp_path / "b"), ckpt_freq=2,
                   fault_plan=f"ckpt-io-error:at={checks_per_save + 2}")
        mgr = CheckpointManager(str(tmp_path / "b"))
        last_good = mgr.latest_step()
        assert last_good is not None and last_good < len(clean_losses)
        # the crashed run recorded losses past the last commit; replay
        # from the commit point must reproduce the tail exactly
        stitched = stitched[:last_good]
        mc = _build_model(seed=123)
        mc.fit(data, batch_size=4, epochs=2, shuffle=True, verbose=0,
               callbacks=[_LossRec(stitched)],
               ckpt_dir=str(tmp_path / "b"), ckpt_freq=2, resume="auto")
        assert stitched == clean_losses
        for k, v in _params(mc).items():
            np.testing.assert_array_equal(v, pa[k]), k

    def test_resume_auto_on_fresh_root_is_fresh_run(self, tmp_path):
        data = _ToyData()
        m = _build_model()
        h = m.fit(data, batch_size=4, epochs=1, shuffle=False, verbose=0,
                  ckpt_dir=str(tmp_path / "fresh"), resume="auto")
        assert len(h["loss"]) == 1

    def test_resume_specific_step_and_missing_step_raises(self, tmp_path):
        root = str(tmp_path / "r")
        data = _ToyData()
        m = _build_model()
        m.fit(data, batch_size=4, epochs=1, shuffle=False, verbose=0,
              ckpt_dir=root, ckpt_freq=2, keep_last_n=10)
        mgr = CheckpointManager(root)
        steps = mgr.all_steps()
        assert steps, "periodic saves expected"
        m2 = _build_model(seed=42)
        m2.fit(data, batch_size=4, epochs=1, shuffle=False, verbose=0,
               ckpt_dir=root, resume=steps[0], keep_last_n=10)
        m3 = _build_model(seed=43)
        with pytest.raises(FileNotFoundError):
            m3.fit(data, batch_size=4, epochs=1, shuffle=False, verbose=0,
                   ckpt_dir=root, resume=9999)

    def test_rng_stream_position_roundtrip(self):
        """The global RNG snapshot restores the exact stream position."""
        prandom.seed(21)
        for _ in range(3):
            prandom.next_key()
        snap = prandom.rng_state_snapshot()
        a = [np.asarray(jax.random.key_data(prandom.next_key()))
             for _ in range(2)]
        prandom.rng_state_restore(snap)
        b = [np.asarray(jax.random.key_data(prandom.next_key()))
             for _ in range(2)]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# ----------------------------------------------- divergence + retry guards

class TestInLoopGuards:
    def test_nan_loss_rolls_back_and_skips(self, tmp_path):
        from paddle_tpu.observability import metric_total

        before = metric_total("paddle_tpu_train_rollbacks_total")
        m = _build_model()
        h = m.fit(_ToyData(), batch_size=4, epochs=2, shuffle=False,
                  verbose=0, ckpt_dir=str(tmp_path), ckpt_freq=2,
                  fault_plan="train-nan-loss:at=5")
        assert metric_total("paddle_tpu_train_rollbacks_total") == before + 1
        assert all(np.isfinite(l) for l in h["loss"])
        for v in _params(m).values():
            assert np.isfinite(v).all()

    def test_loss_spike_guard(self, tmp_path):
        """A FINITE loss spike (poisoned batch: labels blown up 50×) over
        factor×EMA rolls back and skips, and training finishes healthy."""
        from paddle_tpu.observability import metric_total

        before = metric_total("paddle_tpu_train_rollbacks_total")
        data = _ToyData()
        data.y[8:12] = 50.0  # batch index 2 under shuffle=False
        m = _build_model()
        h = m.fit(data, batch_size=4, epochs=1, shuffle=False, verbose=0,
                  ckpt_dir=str(tmp_path), ckpt_freq=1,
                  divergence_factor=5.0)
        assert metric_total("paddle_tpu_train_rollbacks_total") == before + 1
        assert all(np.isfinite(l) for l in h["loss"])

    def test_step_retry_trajectory_identical_to_clean(self):
        """Two transient dispatch faults, retried: the final trajectory
        must equal the fault-free run (grads cleared between attempts)."""
        clean, faulty = [], []
        ma = _build_model()
        ma.fit(_ToyData(), batch_size=4, epochs=1, shuffle=False,
               verbose=0, callbacks=[_LossRec(clean)])
        mb = _build_model()
        mb.fit(_ToyData(), batch_size=4, epochs=1, shuffle=False,
               verbose=0, callbacks=[_LossRec(faulty)],
               max_step_retries=2, retry_backoff=0.001,
               fault_plan="train-step-exception:times=2")
        assert faulty == clean
        for k, v in _params(mb).items():
            np.testing.assert_array_equal(v, _params(ma)[k])

    def test_retries_exhausted_reraises(self):
        m = _build_model()
        with pytest.raises(RuntimeError, match="injected train-step"):
            m.fit(_ToyData(), batch_size=4, epochs=1, shuffle=False,
                  verbose=0, max_step_retries=1, retry_backoff=0.001,
                  fault_plan="train-step-exception")

    def test_metrics_visible_in_prometheus(self):
        from paddle_tpu.observability import render_prometheus

        text = render_prometheus()
        assert "paddle_tpu_train_rollbacks_total" in text
        assert "paddle_tpu_train_checkpoints_total" in text
        assert "paddle_tpu_train_step_retries_total" in text
        assert "paddle_tpu_faults_injected_total" in text


# ------------------------------------------------- serialization satellite

class TestSerializationAtomic:
    def test_failed_save_keeps_previous_file(self, tmp_path, monkeypatch):
        import pickle

        target = str(tmp_path / "m.pdparams")
        paddle.save({"w": paddle.to_tensor(np.ones(3, np.float32))}, target)
        orig = open(target, "rb").read()

        def boom(*a, **k):
            raise OSError("disk died mid-pickle")

        monkeypatch.setattr(pickle, "dump", boom)
        with pytest.raises(OSError):
            paddle.save({"w": paddle.to_tensor(np.zeros(3))}, target)
        assert open(target, "rb").read() == orig  # old file intact
        assert [f for f in os.listdir(tmp_path) if ".tmp-" in f] == []

    def test_roundtrip_still_works(self, tmp_path):
        p = str(tmp_path / "x.pd")
        paddle.save({"a": paddle.to_tensor(np.arange(4.0, dtype=np.float32))}, p)
        out = paddle.load(p)
        np.testing.assert_array_equal(np.asarray(out["a"].numpy()),
                                      np.arange(4.0, dtype=np.float32))


# --------------------------------------------- subprocess kill (real SIGTERM)

_KILL_WORKER = textwrap.dedent("""
    import json, os, signal, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, "__REPO__")
    import numpy as np
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.io import Dataset
    from paddle_tpu.framework import random as prandom
    from paddle_tpu.distributed import TrainingPreempted

    mode, ckpt_dir, out_path, kill_step = sys.argv[1:5]
    kill_step = int(kill_step)

    class DS(Dataset):
        def __init__(self, n=16, d=8, seed=3):
            rng = np.random.default_rng(seed)
            self.x = rng.standard_normal((n, d)).astype(np.float32)
            self.y = rng.standard_normal((n, 1)).astype(np.float32)
        def __getitem__(self, i):
            return self.x[i], self.y[i]
        def __len__(self):
            return len(self.x)

    def build(seed):
        prandom.seed(seed)
        np.random.seed(seed)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        m = Model(net)
        m.prepare(optimizer=popt.Momentum(learning_rate=0.05, momentum=0.9,
                                          parameters=net.parameters()),
                  loss=nn.MSELoss())
        return m

    losses, done = [], [0]

    class Rec(Callback):
        def on_train_batch_end(self, step, logs=None):
            losses.append(float(logs["loss"]))
            done[0] += 1
            if mode == "kill" and done[0] == kill_step:
                os.kill(os.getpid(), signal.SIGTERM)  # REAL preemption

    m = build(7 if mode != "resume" else 1234)
    status = "done"
    try:
        m.fit(DS(), batch_size=4, epochs=2, shuffle=True, verbose=0,
              callbacks=[Rec()], ckpt_dir=ckpt_dir, ckpt_freq=3,
              resume=("auto" if mode == "resume" else None))
    except TrainingPreempted as e:
        status = "preempted:%d" % e.step
    np.savez(out_path + ".npz", **{k: np.asarray(v._data)
             for k, v in m.network.state_dict().items()})
    with open(out_path, "w") as f:
        json.dump({"status": status, "losses": losses}, f)
    print("WORKER_OK", status, flush=True)
""")


@pytest.mark.timeout(300)
def test_real_sigterm_kill_and_resume_bit_identical(tmp_path):
    """Three incarnations of the same training script: clean; killed by a
    REAL SIGTERM at a faultinject-style chosen step; resumed from
    `latest`. Stitched losses and final params must equal clean exactly."""
    script = tmp_path / "worker.py"
    script.write_text(_KILL_WORKER.replace("__REPO__", REPO))
    kill_step = int(np.random.default_rng(5).integers(3, 7))

    def run(mode, ckpt_dir, out):
        r = subprocess.run(
            [sys.executable, str(script), mode, str(ckpt_dir), str(out),
             str(kill_step)],
            cwd=REPO, capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, (mode, r.stdout[-2000:], r.stderr[-2000:])
        assert "WORKER_OK" in r.stdout, r.stdout
        with open(out) as f:
            return json.load(f), np.load(str(out) + ".npz")

    clean, p_clean = run("clean", tmp_path / "ck_a", tmp_path / "out_a")
    killed, _ = run("kill", tmp_path / "ck_b", tmp_path / "out_b")
    assert killed["status"] == f"preempted:{kill_step}"
    assert killed["losses"] == clean["losses"][:kill_step]
    resumed, p_res = run("resume", tmp_path / "ck_b", tmp_path / "out_c")
    assert resumed["status"] == "done"
    assert killed["losses"] + resumed["losses"] == clean["losses"]
    assert sorted(p_clean.files) == sorted(p_res.files)
    for k in p_clean.files:
        np.testing.assert_array_equal(p_clean[k], p_res[k]), k


# ------------------------------------------------- multihost (2 processes)

_MH_WORKER = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)  # 1 device per process
    sys.path.insert(0, "__REPO__")
    import numpy as np
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import CheckpointManager

    assert jax.process_count() == 2
    pidx = jax.process_index()
    root = os.environ["PT_CKPT_ROOT"]
    phase = os.environ["PT_PHASE"]
    mesh = Mesh(jax.devices(), ("dp",))
    sh = NamedSharding(mesh, P("dp"))

    def make_global(local):
        return jax.make_array_from_process_local_data(sh, local)

    def update(x, t):  # deterministic numpy-only "train step": the
        return x - 0.1 * (0.5 * x + t)   # protocol is what's under test

    def fs_barrier(mgr, step, deadline_s=60):
        t0 = time.time()
        while mgr.latest_step() != step:
            assert time.time() - t0 < deadline_s, "commit never landed"
            time.sleep(0.05)

    mgr = CheckpointManager(root, keep_last_n=2)
    local = np.full((2, 4), 1.0 + pidx, np.float32)
    if phase == "first":
        for t in range(3):
            local = update(local, t)
            mgr.save(t + 1, {"w": make_global(local), "t": t + 1})
        fs_barrier(mgr, 3)  # both ranks' markers present => committed
        print("MH_SAVED", pidx, flush=True)
    else:
        step, state = mgr.restore()
        assert step == 3, step
        full = np.asarray(state["w"])
        local = full[pidx * 2:(pidx + 1) * 2]
        for t in range(3, 5):
            local = update(local, t)
            mgr.save(t + 1, {"w": make_global(local), "t": t + 1})
        fs_barrier(mgr, 5)
        expect = np.full((2, 4), 1.0 + pidx, np.float32)
        for t in range(5):
            expect = update(expect, t)
        assert np.array_equal(local, expect), (local, expect)
        step, state = mgr.restore()
        full = np.asarray(state["w"])
        assert np.array_equal(full[pidx * 2:(pidx + 1) * 2], expect)
        print("MH_RESUME_OK", pidx, flush=True)
""")


def _mh_launch(tmp_path, phase, ckpt_root):
    script = tmp_path / f"mh_worker_{phase}.py"
    script.write_text(_MH_WORKER.replace("__REPO__", REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PT_CKPT_ROOT"] = str(ckpt_root)
    env["PT_PHASE"] = phase
    log_dir = tmp_path / f"log_{phase}"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(log_dir), str(script)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=220)
    logs = ""
    for i in range(2):
        p = log_dir / f"workerlog.{i}"
        if p.exists():
            logs += f"--- worker {i}\n" + p.read_text()[-2000:]
    return r, logs


@pytest.mark.multihost
@pytest.mark.timeout(300)
def test_two_process_sharded_save_kill_resume(tmp_path):
    """2 REAL processes: each rank stages its own shards into the SHARED
    staging dir; the commit rename happens only after BOTH markers land.
    The 'first' incarnation dies after step 3; the second resumes from
    `latest` and finishes bit-identical to an uninterrupted trajectory."""
    root = tmp_path / "mh_root"
    r, logs = _mh_launch(tmp_path, "first", root)
    assert r.returncode == 0, f"phase-1 failed\n{r.stderr[-2000:]}\n{logs}"
    assert "MH_SAVED 0" in logs and "MH_SAVED 1" in logs, logs
    assert ckpt.latest_step(str(root)) == 3
    meta = ckpt.read_manifest(str(root))
    assert meta and meta["latest"] == 3
    r, logs = _mh_launch(tmp_path, "resume", root)
    assert r.returncode == 0, f"phase-2 failed\n{r.stderr[-2000:]}\n{logs}"
    assert "MH_RESUME_OK 0" in logs and "MH_RESUME_OK 1" in logs, logs
    assert ckpt.latest_step(str(root)) == 5
