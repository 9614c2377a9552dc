"""Rules of running on the chip (ISSUE 22), checked without one.

* a chip belongs to ONE process: importing the package, the serving stack
  or the launcher initialises no jax backend (so a router or launcher parent
  can stay off the chip), and the launcher refuses several local workers
  unless they are pinned to the CPU;
* ``chip_smoke.py`` has no CPU branch: without an accelerator it exits
  non-zero at once with a message and prints no result, and a phase that
  raises ends the process non-zero — nothing is caught and reported beside
  an exit 0.

(The peak-rate and compile-cache rules sit with their modules' tests:
``test_jaxpr_analysis.py::TestCommCost::test_one_peak_table_and_unknown_kind_raises``,
``test_memory_donation.py::TestCompilationCache``.)
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra, timeout=120):
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str)
            else [sys.executable, *code_or_args])
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra)
    return subprocess.run(args, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.timeout(180)
def test_imports_initialise_no_backend():
    """With a platform jax cannot provide, the first backend init raises.
    The imports must get through; only then does touching a device fail —
    which also shows the probe can fail."""
    code = (
        "import paddle_tpu, paddle_tpu.serving\n"
        "import paddle_tpu.inference.engine\n"
        "import paddle_tpu.distributed.launch.main\n"
        "print('IMPORTS_OK', flush=True)\n"
        "import jax\n"
        "try:\n"
        "    jax.devices()\n"
        "except RuntimeError as e:\n"
        "    print('BACKEND_INIT_RAISED', flush=True)\n")
    r = _run(code, {"JAX_PLATFORMS": "no_such_platform"})
    assert "IMPORTS_OK" in r.stdout, r.stderr[-2000:]
    assert "BACKEND_INIT_RAISED" in r.stdout, (r.stdout, r.stderr[-2000:])


def test_launcher_refuses_several_workers_on_a_chip_host(tmp_path,
                                                         monkeypatch):
    from paddle_tpu.distributed.launch.main import launch, launch_with_master

    script = tmp_path / "w.py"
    script.write_text("raise SystemExit('a worker must not start')\n")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for entry, kw in ((launch, {}),
                      (launch_with_master, {"master_url": "http://x:1"})):
        with pytest.raises(SystemExit, match="a chip belongs to one "
                                             "process"):
            entry(str(script), nproc_per_node=2,
                  log_dir=str(tmp_path / "log"), **kw)
    assert not (tmp_path / "log").exists()  # refused before any spawn
    # one worker a host is the chip form and needs no pin
    script.write_text("")
    assert launch(str(script), nproc_per_node=1, log_dir=None) == 0


@pytest.mark.timeout(120)
def test_chip_smoke_without_accelerator_exits_nonzero_with_no_result():
    r = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode not in (0, None)
    assert "not 'tpu'" in r.stderr and "no CPU branch" in r.stderr
    assert r.stdout.strip() == "", r.stdout  # no result line, no phase ran


def test_chip_smoke_phase_failure_is_a_nonzero_exit(monkeypatch, capsys):
    """Monkeypatch past the device check and make the first phase raise:
    the exception leaves ``main`` (a non-zero exit of the process), the
    second phase never runs, and no result line was printed."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    import jax

    ran = []

    def broken_phase(seed, devices):
        raise chip_smoke.SmokeFailure("serve: a check did not hold")

    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(chip_smoke, "setup_facts", lambda: None)
    monkeypatch.setattr(chip_smoke, "serve_phase", broken_phase)
    monkeypatch.setattr(chip_smoke, "train_phase",
                        lambda *a: ran.append("train"))
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.main([])
    assert not ran
    out = capsys.readouterr().out
    assert '"ok"' not in out
    # and the passing shape of the last line, with the phases stubbed out
    monkeypatch.setattr(chip_smoke, "serve_phase", lambda *a: None)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": 1}}
