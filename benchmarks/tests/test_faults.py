"""``correct`` comes out false when the timed path is broken underneath, and
for the control (the plain reference in fp8 in the program's place). Tiny
configurations on the CPU; the chip's readings at the cells' own sizes are
in PERF.md (tools/control.py)."""
import json

import pytest

from benchmarks.drivers import serve_open_loop, train_steps
from benchmarks.harness import result


def run_cell(run, capsys, workload):
    run.main(["--workload", workload, "--seed", "77", "--seconds", "2",
              "--trace", "0"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failing(line):
    return {n for n, c in line["checks"].items()
            if not c["value"] <= c["limit"]}


def test_train_step_that_returns_its_state_unchanged(tiny_cells, capsys,
                                                     monkeypatch):
    import jax
    import jax.numpy as jnp

    real = train_steps.build_program

    def broken(config, traffic, seed):
        step, params, opt_state = real(config, traffic, seed)
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)

        def unchanged(p, s, ids, labels, n):
            return p, s, step(copy(p), copy(s), ids, labels, n)[2]

        return unchanged, params, opt_state

    monkeypatch.setattr(train_steps, "build_program", broken)
    line = run_cell(tiny_cells, capsys, "gpt2m-pretrain")
    assert line["correct"] is False
    assert {"grad_norm_gap", "update_norm_gap"} <= failing(line)
    assert line["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_step_that_leaves_half_of_the_batch_out(tiny_cells, capsys,
                                                      monkeypatch):
    real = train_steps.build_program

    def broken(config, traffic, seed):
        step, params, opt_state = real(config, traffic, seed)
        half = lambda p, s, ids, labels, n: step(
            p, s, ids[: len(ids) // 2], labels[: len(ids) // 2], n)
        return half, params, opt_state

    monkeypatch.setattr(train_steps, "build_program", broken)
    line = run_cell(tiny_cells, capsys, "gpt2m-pretrain")
    assert line["correct"] is False and failing(line)


def test_served_token_altered_where_it_is_produced(tiny_cells, capsys,
                                                   monkeypatch):
    from paddle_tpu.inference.engine import Engine

    real = Engine.add_request

    def altering(self, prompt, max_new_tokens, on_token=None, **kw):
        seen = [0]

        def alter(toks):
            toks = list(toks)
            if seen[0] <= 2 < seen[0] + len(toks):   # the third token
                i = 2 - seen[0]
                toks[i] = (int(toks[i]) + 1) % self.cfg.vocab_size
            seen[0] += len(toks)
            on_token(toks)

        return real(self, prompt, max_new_tokens,
                    on_token=alter if on_token else None, **kw)

    monkeypatch.setattr(Engine, "add_request", altering)
    line = run_cell(tiny_cells, capsys, "mistral7b-chat")
    assert line["correct"] is False
    assert "served_logit_gap" in failing(line)


def test_train_control_and_planted_faults_are_not_correct(tiny_cells):
    """The reference in fp8, put in the program's place, fails the cell's
    own limits; so does the reference with half of the batch left out, and
    with its state left unchanged (both norms then read 1)."""
    cell = tiny_cells.loader.load_cell("gpt2m-pretrain")
    readings = train_steps.control(dict(cell, seed=5))
    assert set(readings) == {"control_fp8", "fault_half_batch",
                             "fault_state_unchanged"}
    for name, read in readings.items():
        assert read["correct"] is False, (name, read)
    still = readings["fault_state_unchanged"]
    assert still["grad_norm_gap"] == still["update_norm_gap"] == 1.0
    assert still["loss1_gap"] == 0.0 < still["loss3_gap"]


def test_serve_control_fp8_is_not_correct(tiny_cells):
    cell = tiny_cells.loader.load_cell("mistral7b-chat")
    import jax

    ctx = dict(cell, seed=9, trace=False, seconds=3.0,
               devices=jax.devices()[:1], chips=1, t_start=0.0)
    readings = serve_open_loop.control(ctx)
    limit = cell["cell"]["limits"]["served_logit_gap"]
    assert readings["program"] <= limit
    assert readings["control_fp8"] > limit
    assert readings["fault_token_altered"] > limit


def test_is_correct():
    assert result.is_correct([("a", 0.1, 0.2)])
    assert not result.is_correct([("a", 0.3, 0.2)])
    assert not result.is_correct([("a", float("nan"), 0.2)])
    assert not result.is_correct([])
