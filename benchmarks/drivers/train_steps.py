"""Driver ``train_steps``: the compiled training step, back to back.

One object is built in set-up (the jitted step of ``functional_call`` and
``optimizer.AdamW.apply_gradients_tree`` with its parameters and optimizer
state), driven from the seed through its first three steps by the window's
own call and feed, and handed on to the window. The comparison that decides
``correct`` reads those three steps against the plain reference once the
window has closed and the program's state is freed.
"""
import faulthandler
import sys
import time
from collections import deque

import numpy as np

from ..harness import device, loader, trace_window, weights
from ..harness.norms import block_norms

CHECK_STEPS = 3
STALL_S = 1.0  # a loop iteration this long gets every thread's stack printed


def build_program(config, traffic, seed):
    """(step, params, opt_state): the configuration's model holding the
    seeded leaves; the step is ``chip_smoke.make_train_step``'s, the
    optimizer's settings the traffic's."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import optimizer
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import functional_call, param_arrays

    o = traffic["optimizer"]
    model = loader.find("builders", config["builder"]).build(
        config, seed, train=True)
    opt = optimizer.AdamW(learning_rate=o["lr"], beta1=o["beta1"],
                          beta2=o["beta2"], epsilon=o["eps"],
                          weight_decay=o["weight_decay"],
                          multi_precision=True)

    def step(params, opt_state, ids, labels, step_no):
        def loss_fn(p):
            logits = functional_call(model, p, Tensor._wrap(ids))
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(
                logits, labels[..., None], axis=-1)[..., 0]
            return jnp.mean(logz - gold.astype(jnp.float32))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_p, new_s = opt.apply_gradients_tree(params, grads, opt_state,
                                                o["lr"], step_no)
        return new_p, new_s, loss

    params = param_arrays(model)
    return (jax.jit(step, donate_argnums=(0, 1)), params,
            opt.init_state_tree(params))


def feed(config, traffic, seed, step_index):
    """The batch of step ``step_index`` (from 1): seeded token ids made on
    the host, every row different, (ids, labels) = (x[:, :-1], x[:, 1:])."""
    rng = np.random.default_rng([int(seed), int(step_index)])
    x = rng.integers(0, config["vocab_size"],
                     (traffic["batch"], traffic["seq"] + 1)).astype(np.int32)
    return x[:, :-1], x[:, 1:]


def _moment_norms(opt_state, beta1, blocks):
    """Norm by leaf block of the first gradient as the optimizer got it,
    worked out from its state after one step: moment1 = (1 - beta1) g."""
    import jax

    fn = jax.jit(lambda st: block_norms(
        {k: v["moment1"] / (1 - beta1) for k, v in st.items()}, blocks))
    return {k: float(v) for k, v in jax.device_get(fn(opt_state)).items()}


def _update_norms(params, opt_state, seed, blocks):
    """Norm by leaf block of (parameters now - the seed's leaves), on the
    fp32 master where the optimizer keeps one."""
    import jax
    import jax.numpy as jnp

    names = sorted(params)
    specs = [(n, params[n].shape, params[n].dtype) for n in names]
    words = weights.words_for(seed, names)

    def fn(words, params, st):
        out = {}
        for i, (n, shape, dt) in enumerate(specs):
            now = st[n].get("master", params[n]).astype(jnp.float32)
            was = weights.leaf_from_words(words[i], n, tuple(shape), dt)
            out[n] = now - was.astype(jnp.float32)
        return block_norms(out, blocks)

    return {k: float(v) for k, v in jax.device_get(
        jax.jit(fn)(words, params, opt_state)).items()}


def worst_leaf_gap(got, ref, leave_out=()):
    """Largest |got - ref| over the leaves, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns (gap, leaf)."""
    median = float(np.median(list(ref.values())))
    worst = (0.0, None)
    for k, r in ref.items():
        if k in leave_out:
            continue
        gap = abs(got[k] - r) / max(r, median)
        if not gap <= worst[0]:  # a NaN gap is the worst
            worst = (gap, k)
    return worst


def numbers(program, reference):
    """{name: value} of everything read: each step's loss gap, the first
    gradient's norm and the parameters' change after the last step, by the
    worst leaf block. Leaf blocks whose reference gradient is under a
    thousandth of the median block's move under Adam by round-off alone and
    are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"],
                                   reference["losses"]), start=1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
        print(f"check: loss {i} program {a:.6f} reference {b:.6f} gap "
              f"{out[f'loss{i}_gap']:.3e}", flush=True)
    g_ref = reference["grad_norms"]
    out["grad_norm_gap"], leaf = worst_leaf_gap(program["grad_norms"], g_ref)
    print(f"check: worst gradient-norm leaf block {leaf}", flush=True)
    floor = 1e-3 * float(np.median(list(g_ref.values())))
    still = {k for k, v in g_ref.items() if v < floor}
    out["update_norm_gap"], leaf = worst_leaf_gap(
        program["update_norms"], reference["update_norms"], leave_out=still)
    print(f"check: worst update-norm leaf block {leaf}; {len(still)} left "
          f"out (gradient nought to rounding), e.g. {sorted(still)[:2]}",
          flush=True)
    return out


def compare(program, reference, limits):
    """[(name, value, limit)]: every number the cell's file gives a limit
    is held to it; the others are printed only. Which numbers carry a limit
    follows from the readings of sound runs, the control and the faults at
    the cell's own size (PERF.md)."""
    read = numbers(program, reference)
    return [(n, read[n], limit) for n, limit in limits.items()]


def run(ctx):
    import jax
    import jax.numpy as jnp

    config, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    devices = ctx["devices"]
    t = time.perf_counter()
    step, params, opt_state = build_program(config, traffic, seed)
    device.memory_line("after the model and optimizer state are built",
                       devices)
    print(f"setup: model, seeded leaves and optimizer state built in "
          f"{time.perf_counter() - t:.1f} s", flush=True)

    def one(i, params, opt_state):
        ids, labels = feed(config, traffic, seed, i)
        return step(params, opt_state, ids, labels, jnp.float32(i))

    # set-up: the first steps through the window's own call and feed (the
    # first compiles or loads the program), with the readings the
    # comparison needs taken between them
    program, blocks = {"losses": []}, cell["leaf_blocks"]
    for i in range(1, CHECK_STEPS + 1):
        t = time.perf_counter()
        params, opt_state, loss = one(i, params, opt_state)
        program["losses"].append(float(loss))
        print(f"setup: step {i} in {time.perf_counter() - t:.2f} s"
              + (" (compiles or loads the program)" if i == 1 else ""),
              flush=True)
        if i == 1:
            program["grad_norms"] = _moment_norms(
                opt_state, traffic["optimizer"]["beta1"], blocks)
    program["update_norms"] = _update_norms(params, opt_state, seed, blocks)
    print("first losses " + " ".join(f"{x:.5f}" for x in program["losses"]),
          flush=True)

    tokens_per_step = traffic["batch"] * traffic["seq"]
    tracer = trace_window.TraceWindow(ctx) if ctx["trace"] else None
    steps_done, i, traced_steps = 0, CHECK_STEPS, 0
    if tracer:
        tracer.start()
    setup_s = time.perf_counter() - ctx["t_start"]
    print(f"setup: {setup_s:.3f} s from process start to window start",
          flush=True)
    t0 = time.perf_counter()
    pending, paused = deque(), 0.0
    depth = int(traffic["steps_in_flight"])
    slowest, t_prev = (0.0, 0), t0  # longest time between two steps' ends
    # steps are launched while the window is open, up to ``depth`` of them
    # ahead of the one being waited for (a training loop that reads its
    # loss every so many steps): the host's feed overlaps the device's
    # steps, and a pause of the host shorter than the queue costs the
    # device nothing. The window closes when the last step launched in it
    # has finished. An iteration that takes over STALL_S (five steps) gets
    # the stack of every thread printed, to say where the host stood.
    while True:
        faulthandler.dump_traceback_later(STALL_S, file=sys.__stderr__)
        now = time.perf_counter() - t0 - paused
        if tracer and tracer.running and now >= tracer.seconds:
            # every step launched so far is waited for (each needs the one
            # before it), then the clock stands still while the profiler
            # writes its trace out
            faulthandler.cancel_dump_traceback_later()
            if pending:
                jax.block_until_ready(pending[-1])
            t_stop = time.perf_counter()
            tracer.stop()
            traced_steps = i - CHECK_STEPS
            paused = time.perf_counter() - t_stop
            t_prev = time.perf_counter()
        launch = now < seconds
        if launch:
            i += 1
            with trace_window.span("bench.feed_and_launch"):
                params, opt_state, loss = one(i, params, opt_state)
            pending.append(loss)
        if pending and (not launch or len(pending) > depth):
            with trace_window.span("bench.wait_step"):
                jax.block_until_ready(pending.popleft())
            steps_done += 1
            t_now = time.perf_counter()
            slowest = max(slowest, (t_now - t_prev, steps_done))
            t_prev = t_now
        if not launch and not pending:
            break
    faulthandler.cancel_dump_traceback_later()
    window_s = time.perf_counter() - t0 - paused
    if tracer and tracer.running:
        tracer.stop()
        traced_steps = steps_done
    peak = device.memory_peak_bytes(devices)
    device.memory_line("after the window", devices)
    last_loss = float(loss)
    print(f"window: {steps_done} steps in {window_s:.4f} s, last loss "
          f"{last_loss:.5f}; longest time between two steps' ends as the "
          f"host saw them {slowest[0]:.3f} s (step {slowest[1]}), up to "
          f"{depth} steps in flight", flush=True)

    # free the program's state, then follow the same steps plainly
    del params, opt_state, loss, pending
    ref_mod = loader.find("reference", config["reference"])
    batches = [feed(config, traffic, seed, j)
               for j in range(1, CHECK_STEPS + 1)]
    t_ref = time.perf_counter()
    reference = ref_mod.train_readings(config, seed, batches,
                                       traffic["optimizer"], blocks)
    print(f"reference: {CHECK_STEPS} steps in "
          f"{time.perf_counter() - t_ref:.1f} s, losses "
          + " ".join(f"{x:.5f}" for x in reference["losses"]), flush=True)
    checks = compare(program, reference, cell["limits"])
    checks.append(("last_loss_finite", 0.0 if np.isfinite(last_loss)
                   else float("nan"), 0.0))
    tokens = steps_done * tokens_per_step
    return {
        "attempted": steps_done, "failed": 0, "checks": checks,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "setup_s": setup_s},
        "facts": {"window_s": window_s, "tokens": tokens,
                  "steps": steps_done, "batch": traffic["batch"],
                  "seq": traffic["seq"],
                  "traced_steps": traced_steps},
        "trace": tracer.reduced() if tracer else None,
    }


def control(ctx):
    """The control and the faults of this kind of cell, read at the cell's
    own size without the program: the reference in fp8, with half of each
    batch left out, and with its state left unchanged, each put in the
    program's place and compared with the float32 reference. Returns, for
    each, every number read and whether the cell's limits call it correct
    (none may be)."""
    from ..harness import result

    config, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    seed, blocks = ctx["seed"], cell["leaf_blocks"]
    ref_mod = loader.find("reference", config["reference"])
    batches = [feed(config, traffic, seed, j)
               for j in range(1, CHECK_STEPS + 1)]
    follow = lambda **kw: ref_mod.train_readings(
        config, seed, batches, traffic["optimizer"], blocks, **kw)
    reference = follow()
    readings = {}
    for name, kw in (("control_fp8", {"precision": "fp8"}),
                     ("fault_half_batch", {"fault": "half_batch"}),
                     ("fault_state_unchanged",
                      {"fault": "state_unchanged"})):
        planted = follow(**kw)
        readings[name] = dict(
            numbers(planted, reference),
            correct=result.is_correct(
                compare(planted, reference, cell["limits"])))
    return readings
