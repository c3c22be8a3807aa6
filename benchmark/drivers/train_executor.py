"""Training steps through ``ht.Executor``, fed from the host.

Traffic parameters (``traffic/<mix>.json``): ``seq_len``,
``learning_rate``, ``pool_batches`` (distinct host batches made from
the seed before the window), ``warmup_steps``, ``loss_lag`` (how many
steps back the loss is read: the step just issued is never waited on),
``trace_seconds`` and whatever the family's feed needs. The per-chip
batch is the configuration's (``sizing.per_chip_batch``).

What it measures, ``--trace 0``: steps are issued back to back for
``--seconds``; the window ends in ``block_until_ready`` on the last
step, so every step counted has finished inside it.
``train_tokens_per_s_per_chip`` = steps x global batch x seq_len /
window seconds / chips.

``--trace 1``: the same loop for ``trace_seconds`` under
``jax.profiler``, with the host's part wrapped in ``bench.*``
annotations; nothing end to end is reported from it.

``correct``: before the first training step the program's
inference-mode outputs (the ``validate`` sub-executor: same graph and
kernels, dropout off) on the first ``check_sequences`` sequences of the
first batch must agree with the family's plain float32 reference on
the same weights — the loss, and the scores over the vocabulary at
EVERY position (``harness/stats.py:row_errors``, worst position); every
loss read afterwards must be finite; no program may compile inside the
window.
"""
import time

import numpy as np

from benchmark.harness import compiles, device as device_info, stats
from benchmark.harness.outcome import Outcome
from benchmark.trace import xplane


def _loss(out):
    return float(np.asarray(out[0].asnumpy()))


def agrees(session, got, want, log):
    """The program's validate outputs ``got`` = [loss, *arrays] (hetu
    arrays) against the reference's ``want`` = (loss, [arrays])."""
    loss, want_loss = _loss(got), want[0]
    ok = abs(loss - want_loss) <= session.loss_tolerance * abs(want_loss)
    log({"check": "validate_loss_vs_reference", "program": loss,
         "reference": want_loss,
         "relative_tolerance": session.loss_tolerance, "ok": ok})
    if len(got) - 1 != len(want[1]):
        raise ValueError("validate group and reference disagree on the "
                         "number of outputs")
    for out, ref in zip(got[1:], want[1]):
        errors = stats.row_errors(out.asnumpy(), ref)
        good = bool(errors.max() <= session.output_tolerance)
        log({"check": "validate_outputs_vs_reference",
             "shape": list(ref.shape), "reference_std": float(ref.std()),
             "row_error_max": float(errors.max()),
             "row_error_p50": float(np.median(errors)),
             "worst_row": int(errors.argmax()),
             "tolerance": session.output_tolerance, "ok": good})
        ok = ok and good
    return ok


def _tiles():
    from hetu_tpu.tune.autotune import get_table
    return {k: list(v) for k, v in get_table().chosen("flash").items()}


def run(cell, opts):
    import jax
    from jax.profiler import TraceAnnotation

    phases = compiles.Phases(opts.process_start)
    traffic, config = cell.traffic, cell.config
    family = cell.family()
    phases.mark("imports_and_device")
    chips = cell.chips
    batch = config["sizing"]["per_chip_batch"] * chips
    session = family.build_train(config, traffic, opts.seed)
    executor = session.executor
    compiled = executor.subexecutors["default"].compiled
    phases.mark("build_graph_and_parameters")

    rng = np.random.RandomState(opts.seed % (2 ** 32))
    pool = [session.make_batch(rng, batch)
            for _ in range(traffic["pool_batches"])]
    feeds = [dict(zip(session.feed_nodes, values)) for values in pool]

    phases.mark("batch_pool")

    # -- correctness: the program against the plain reference ----------
    n_check = traffic["check_sequences"]
    head = [v[:n_check] for v in pool[0]]
    got = executor.run(
        "validate", feed_dict=dict(zip(session.feed_nodes, head)))
    got[0].jax_array.block_until_ready()
    phases.mark("validate_pass")
    want = session.reference(session.params_by_name(), head)
    phases.mark("reference")
    agree = agrees(session, got, want, opts.log)
    del got, want
    phases.mark("compare")

    # -- warm-up: the one shape the window uses ------------------------
    losses = [_loss(executor.run(feed_dict=feeds[i % len(feeds)]))
              for i in range(traffic["warmup_steps"])]
    compiles_before = len(compiled)
    phases.mark("warmup_steps")
    opts.log({"setup_phases_s": phases.rows})
    backend_before = opts.compiles.backend_compiles

    lag = traffic["loss_lag"]
    seconds = traffic["trace_seconds"] if opts.trace else opts.seconds
    if opts.rehearse:
        seconds = min(seconds, 2.0)
    pending, host_ms, steps = [], [], 0
    if opts.trace:
        xplane.start(opts.trace_dir)
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        setup_s = t0 - opts.process_start
        while time.perf_counter() - t0 < seconds:
            with TraceAnnotation("bench.feed"):
                feed = feeds[steps % len(feeds)]
            t_run = time.perf_counter()
            with TraceAnnotation("bench.executor_run"):
                pending.append(executor.run(feed_dict=feed))
            host_ms.append((time.perf_counter() - t_run) * 1e3)
            steps += 1
            if len(pending) > lag:
                with TraceAnnotation("bench.read_loss"):
                    losses.append(_loss(pending.pop(0)))
        with TraceAnnotation("bench.block_until_ready"):
            pending[-1][0].jax_array.block_until_ready()
        window_s = time.perf_counter() - t0
    committed = device_info.committed_bytes(opts.devices)
    if opts.trace:
        jax.profiler.stop_trace()
    losses += [_loss(out) for out in pending]

    compiles_after = len(compiled)
    backend_in_window = opts.compiles.backend_compiles \
        - backend_before
    finite = bool(np.all(np.isfinite(losses)))
    tokens = steps * batch * session.tokens_per_sequence
    rate = tokens / window_s / chips
    peak = None if opts.rehearse else device_info.peaks(
        opts.devices[0].device_kind)["bf16_flops_per_s"]
    flops_per_token = family.train_flops_per_token(
        config, traffic["seq_len"])
    opts.log({"steps": steps, "window_s": window_s, "global_batch": batch,
              "tokens_per_s_per_chip": rate,
              "mfu": rate * flops_per_token / peak if peak else None,
              "flops_per_token": flops_per_token,
              "first_loss": losses[0], "last_loss": losses[-1],
              "jit_compiles_before": compiles_before,
              "jit_compiles_after": compiles_after,
              "backend_compiles_in_window": backend_in_window,
              "flash_tiles": _tiles()})
    return Outcome(
        correct=agree and finite and compiles_after == compiles_before
        and backend_in_window == 0,
        attempted=steps, failed=0 if finite else
        int(np.sum(~np.isfinite(losses))),
        setup_s=setup_s,
        end_to_end={"train_tokens_per_s_per_chip": rate},
        facts={"driver": "train_executor", "steps": steps,
               "window_s": window_s, "host_ms_per_step": host_ms,
               "chips": chips, "config": config, "traffic": traffic,
               "global_batch": batch,
               "flash_calls": family.flash_calls_per_step(
                   config, traffic, batch // chips),
               "device_kind": opts.devices[0].device_kind},
        traced=opts.trace, committed_bytes=committed)
