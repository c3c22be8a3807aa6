"""``trinity_afmoe`` family (grouped-query attention over a sliding
window on most layers and over everything on every fourth, a gate on
attention's output, sandwich norms, routed and shared experts): from a
configuration file to what the serving driver runs. Serving only: the
program has no training graph for these blocks (``ROADMAP.md``).

A configuration of this family is ONE CHIP'S SHARE of a deployment, as
``families/sarvam_mla.py`` has it: ``deployment`` says which routed
experts and which rows of the vocabulary this chip holds; the file's own
``num_experts`` and ``vocab_size`` are the held ones, the router stays
as wide as the published model.

Offers the drivers ``build_engine``, ``engine_reference_logits`` and
``check_generated``. **What ``correct`` holds the engine to**, in three
parts:

*The timed path's tokens.* Routing is discrete, so the engine hands
back, beside every generated token, the RECORD of the row that decided
it (``Future.token_records``: each expert layer's picks and the row's
best logit), and the reference (``reference/trinity_afmoe.py``, float32
at the highest precision) runs FORCED onto those picks over prompt +
generated tokens. Every checked row is held to three limits: the chosen
token's logit within ``LOGIT_TOLERANCE`` of the forced reference's best;
the engine's own best logit within ``VALUE_TOLERANCE`` of the forced
reference's logit for that token; the engine's picks within
``PICK_DELTA`` of the reference's own in ``s + b``. The checked prompts
lie on both sides of the window (``traffic/longchat-r50.json`` chooses
its population so), so the band's edge in the flash call, the ring's
wrap and the short path are all inside what is compared. The 8-bit
control (``all_8bit``) has to fail.

*Both kinds of layer's attention as this backend runs it*, in a SIDE
PASS of the check's own (one row, its own ``PagedKVCache``: not the
timed engine's programs with many rows live, which the first part and
the tier-1 tests answer for). The reference's NORMED rows of the first
sliding and the first full layer, for the first checked request longer
than the ring, go through the program's ``window_moe.attention_inputs``
with the engine's parameters (the four projections, the norms over each
head, the rotation where the program's ``layer_types`` says so), then
``window_moe.prefill_context`` (the flash call, with the band on a
sliding layer, on a TPU) and a generated token at a time
``ops/attention.py:grouped_ring_decode_attention`` over a ring and
``grouped_decode_attention`` over a block table of
``serving/kvcache.py``, all in the serving dtype: every row's context
within ``ATTENTION_TOLERANCE`` (relative) of the reference's. One key
of 4,096 moves no logit by a readable amount; here it moves a row by a
twentieth, so this part answers for the window's edges and the ring's
mask — and for the ROTATION: one on the full layer moves a logit by
0.04-0.20, which the first part's limits do not always see, and a row
of that layer by a third; since the rotation here is the program's
own, a program that rotated its full layer would fail against the sound
reference as the mutant fails against the sound program.

*The router and the routed sum as this backend runs them*
(``ops/moe.py``), on the reference's inputs to the first expert layer
at the first request's checked rows: picks equal wherever the margin is
over ``ROUTER_MARGIN``, weights to ``ROUTER_TOLERANCE``, the routed sum
to ``EXPERT_TOLERANCE`` (relative RMS), which the held experts in 8
bits have to fail.

Each of the reference's ``MUTANTS`` is run through the part that
answers for it and logged with its reading: a part that passes a mutant
fails the run.
"""
import numpy as np

# how far picks lie from a router's own top k: the same reading
from benchmark.families.sarvam_mla import pick_distance
from benchmark.harness.session import executor_seed
from benchmark.reference import trinity_afmoe as reference
# what the parent lacks: it fails the cell here, in seconds
from hetu_tpu.models import window_moe

# Each limit lies between the sound engine's largest reading and the
# control's (or the mutant's) smallest, about their geometric mean (my
# chip runs, PR 47: 9 runs, 9 seeds, 36 checked requests of 32 rows; the
# control and the mutants on 32 rows a run; PERF.md section 4):
# chosen token under the forced best: 0.026 | control 0.148
LOGIT_TOLERANCE = 0.06
# engine's best logit against the forced reference's: 0.049 | 0.234
VALUE_TOLERANCE = 0.10
# the engine's picks from the reference's own, in s + b: 0.0028 | 0.0165
PICK_DELTA = 0.007
# relative, a row of a layer's context, the program's side in bfloat16
# (call E: 3 seeds, prompts of 4,200 / 9,000 / 14,000; call F: the
# cell's 7 runs): the sliding layer 0.0060-0.0067, the full one
# 0.0032-0.0035 | one key of 4,096 too many or too few 0.0309-0.0845,
# the ring unmasked 0.062-0.087, a rotation on the full layer
# 0.301-0.335
ATTENTION_TOLERANCE = 0.015
# the routed sum (relative RMS): 0.0034-0.0036 | the held experts in 8
# bits 0.0575-0.0589 (call E, both expert layers checked, 3 seeds; call
# F, 7 runs). The
# router is float32 in the configuration and in the control, so its
# weights read 0.0 and their limit is held against ``no_route_scale``
# alone (1.448)
ROUTER_MARGIN = 1e-4
ROUTER_TOLERANCE = 1e-3     # relative, on a pick's weight
EXPERT_TOLERANCE = 0.015


def model_config(config, dtype=None):
    d = config["deployment"]
    return window_moe.WindowMoEConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        layer_types=config["layer_types"],
        sliding_window=config["sliding_window"],
        num_dense_layers=config["num_dense_layers"],
        num_experts=d["num_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["num_shared_experts"],
        route_norm=config["route_norm"],
        route_scale=config["route_scale"],
        mup_enabled=config["mup_enabled"],
        experts_held=(d["experts_first"], config["num_experts"]),
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=dtype or config["serve_dtype"])


def train_flops_per_token(config, seq_len):
    raise NotImplementedError(
        "the trinity_afmoe family is serving only: the program has no "
        "training graph for window attention or expert layers")


def seeded_weights(config, seed):
    """Every serving parameter, made on the device from the seed, one
    jitted call a (shape, kind), as the file's ``assumed.weights`` says
    (``families/sarvam_mla.py`` has the reasons for the generator)."""
    import jax
    import jax.numpy as jnp

    a = config["assumed"]
    dtype = jnp.dtype(config["serve_dtype"])
    key = jax.random.key(executor_seed(seed), impl="rbg")
    makers = {}

    def make(shape, kind):
        if (shape, kind) not in makers:
            if kind == "norm":
                fn = lambda k: jnp.ones(shape, jnp.float32)     # noqa: E731
            elif kind == "router_bias":
                fn = lambda k: a["router_bias_std"] * jax.random.normal(  # noqa: E731
                    k, shape, jnp.float32)
            else:
                out = jnp.float32 if kind == "router" else dtype
                fn = lambda k: (a["initializer_std"] * jax.random.normal(  # noqa: E731
                    k, shape, jnp.float32)).astype(dtype).astype(out)
            makers[shape, kind] = jax.jit(fn)
        return makers[shape, kind]

    shapes = window_moe.window_moe_param_shapes(model_config(config))
    return {name: make(shape, kind)(jax.random.fold_in(key, n))
            for n, (name, (shape, kind)) in enumerate(sorted(
                shapes.items()))}


def build_engine(config, engine_kw, seed):
    from hetu_tpu.serving.scheduler import ContinuousBatchingEngine
    weights = seeded_weights(config, seed)
    engine = ContinuousBatchingEngine(
        model_config(config), weights.__getitem__, **engine_kw)
    return engine, weights


def engine_reference_logits(config, weights, tokens, positions,
                            pad_to=None):
    return reference.logits_at(weights, config, tokens, positions, pad_to)


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def _pad(n):
    """One compiled reference per 1024 tokens of length."""
    return -(-n // 1024) * 1024


def forced_readings(config, weights, prompt, out, record, mutant=None,
                    force=True, want_layers=()):
    """One checked request against the reference forced onto the
    engine's picks (``force=False``: running free): per generated
    token, the chosen token's ``gap`` under the reference's best, the
    ``value`` error of the engine's own best logit, and the
    ``pick_distance`` of the engine's picks, the largest over the
    expert layers; and what the reference's layers saw."""
    p, new = len(prompt), len(out)
    rows = np.arange(p - 1, p - 1 + new)
    tokens = np.concatenate([prompt, out[:-1]])
    logits, layers, *rest = reference.forward(
        weights, config, tokens, rows, _pad(len(tokens)), mutant,
        record["router_picks"] if force else None, want_layers=want_layers)
    chosen = logits[np.arange(new), out]
    distance = np.max([pick_distance(layer["scores"],
                                     record["router_picks"][:, i])
                       for i, layer in enumerate(layers)], axis=0)
    return ({"gap": logits.max(axis=-1) - chosen,
             "value": np.abs(record["best_logit"] - chosen),
             "pick_distance": distance}, layers, *rest)


def within_limits(readings):
    # ``not (a <= b)``: a reading that is not a number fails
    return bool((readings["gap"] <= LOGIT_TOLERANCE).all()
                and (readings["value"] <= VALUE_TOLERANCE).all()
                and (readings["pick_distance"] <= PICK_DELTA).all())


def _worst(readings):
    return {"worst_" + k: float(np.max(v)) for k, v in readings.items()}


def program_attention(config, weights, layer, h, p, block_size=16):
    """The PROGRAM's attention of one layer, in the serving dtype, on
    the reference's normed rows ``h [T, hidden]`` as the engine walks
    them: the four projections, the norms over each head and — where
    the program's configuration says the layer slides — the rotation,
    all by ``window_moe.attention_inputs`` on the engine's own
    parameters; a prefill over the first ``p`` rows padded to their
    bucket (``window_moe.prefill_context``), its rows written to a
    ``PagedKVCache`` (a sliding layer: the last window of them, to a
    ring), then a step a row (``ops/attention.py``:
    ``grouped_ring_decode_attention`` / ``grouped_decode_attention``).
    Returns ``([T, heads x D]`` float32 numpy, the ring's slots)."""
    import functools
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import attention as ops
    from hetu_tpu.serving.kvcache import PagedKVCache
    cfg = model_config(config)
    dtype = jnp.dtype(cfg.dtype)
    # this layer's parameters as the program holds them: matrices in the
    # model's dtype, norms float32 (``window_moe_serving_params``)
    w = reference.layer_weights(weights, layer)
    blk = {"qkvg": jnp.asarray(w["qkvg"], dtype),
           "q_norm": jnp.asarray(w["q_norm"], jnp.float32),
           "k_norm": jnp.asarray(w["k_norm"], jnp.float32)}
    sliding = cfg.is_sliding(layer)
    t = h.shape[0]
    cache = PagedKVCache(cfg, num_blocks=-(-(t + 1) // block_size),
                         block_size=block_size, state_slots=1,
                         telemetry=False)
    cache.add_seq(0, t + 1)
    bucket = 1 << (p - 1).bit_length()
    context = -(-t // block_size) * block_size  # the table, whole blocks

    def inputs(blk, h):
        cos, sin = window_moe._rope_tables(cfg, jnp.arange(t))
        return window_moe.attention_inputs(cfg, blk, h, cos, sin,
                                           sliding)[:3]

    def padded(x):
        return jnp.concatenate([x[:p], jnp.broadcast_to(
            x[p - 1], (bucket - p, *x.shape[1:]))])[None]

    q, k, v = jax.jit(inputs)(blk, jnp.asarray(h, dtype))
    ctx = jax.jit(lambda q, k, v: window_moe.prefill_context(
        cfg, q, k, v, sliding))(padded(q), padded(k), padded(v))
    outs = [np.asarray(ctx[0, :p], np.float32).reshape(p, -1)]
    first = max(0, p - cfg.sliding_window) if sliding else 0
    slots = cache.window_slot_mapping if sliding else cache.slot_mapping
    pools = window_moe._write_kv(
        cache.pools[layer], jnp.asarray(slots(0, first, p)),
        k[first:p], v[first:p])
    if sliding:
        read = jnp.asarray(cache.ring_slots([0]))
        step = jax.jit(functools.partial(
            ops.grouped_ring_decode_attention, window=cfg.sliding_window,
            sm_scale=window_moe._scale(cfg)))
    else:
        read = jnp.asarray(cache.gather_slots([0], context))
        step = jax.jit(functools.partial(
            ops.grouped_decode_attention, sm_scale=window_moe._scale(cfg)))
    for i in range(p, t):
        pools = window_moe._write_kv(
            pools, jnp.asarray(slots(0, i, i + 1)), k[i:i + 1], v[i:i + 1])
        out = step(q[i:i + 1], pools["k"], pools["v"], read,
                   jnp.asarray([i], jnp.int32))
        outs.append(np.asarray(out, np.float32).reshape(1, -1))
    return np.concatenate(outs), cache.ring * block_size


def attention_readings(config, att, p, ring, program, mutant=None,
                       sliding=True):
    """The second part's reading: the relative error of each row of the
    program's attention of one layer against the reference's (or a
    fault of it) on the same ``q`` / ``k`` / ``v``."""
    want = reference.attention_context(config, att, sliding, mutant, p,
                                       ring)
    return {"attention_error": np.linalg.norm(program - want, axis=1)
            / np.linalg.norm(want, axis=1)}


def program_parts(config, weights, layer, x):
    """The PROGRAM's router and expert layer (``hetu_tpu/ops/moe.py``,
    as this backend runs them) on rows ``x [n, hidden]``: ``(experts,
    weights, routed sum)``."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import moe
    cfg = model_config(config)
    w = reference.layer_weights(weights, layer)

    def parts(x, w):
        experts, wts, _ = moe.route(
            x, w["router"], w["router_bias"], cfg.num_experts_per_tok,
            cfg.route_scale)
        routed, _ = moe.held_experts(
            x, experts, wts, jnp.ones(x.shape[0], bool),
            w["experts_gate_up"], w["experts_down"],
            first=cfg.experts_held[0])
        return experts, wts, routed

    return [np.asarray(a) for a in jax.jit(parts)(
        jnp.asarray(x, jnp.dtype(config["serve_dtype"])), w)]


def parts_against_reference(config, weights, layer, x, mutant=None):
    """Readings of the third part on rows ``x`` (rounded to the serving
    dtype first, so both see identical numbers)."""
    import jax.numpy as jnp
    x = np.asarray(jnp.asarray(x, jnp.dtype(config["serve_dtype"]))
                   .astype(jnp.float32))
    experts, wts, routed = program_parts(config, weights, layer, x)
    r_experts, r_wts, r_margin, r_routed = reference.expert_layer_parts(
        weights, config, layer, x, mutant)
    clear = r_margin > ROUTER_MARGIN
    same = (np.sort(experts, axis=1) == np.sort(r_experts, axis=1)).all(
        axis=1)
    wide = np.zeros((len(x), config["deployment"]["num_routed_experts"]))
    r_wide = wide.copy()
    np.put_along_axis(wide, experts, wts, axis=1)
    np.put_along_axis(r_wide, r_experts, r_wts, axis=1)
    agree = same & clear
    weight_error = float(np.max(
        np.abs(wide - r_wide)[agree] / np.maximum(r_wide[agree], 1e-6)
        * (r_wide[agree] > 0), initial=0.0))
    moved = np.sqrt(np.mean(np.square(r_routed[agree])))
    routed_error = float(
        np.sqrt(np.mean(np.square(routed[agree] - r_routed[agree])))
        / moved) if agree.any() and moved > 0 else float("nan")
    reading = {"layer": layer, "rows": int(len(x)),
               "rows_clear": int(clear.sum()),
               "picks_differ_on_clear_rows": int((clear & ~same).sum()),
               "weight_error": weight_error, "routed_error": routed_error}
    ok = (reading["picks_differ_on_clear_rows"] == 0
          and weight_error <= ROUTER_TOLERANCE
          and routed_error <= EXPERT_TOLERANCE)
    return ok, reading


def check_generated(config, weights, prompts, outs, records, log,
                    block_size=16):
    """``correct`` of this family (the module docstring says what it
    holds the engine to). ``prompts`` / ``outs`` / ``records``: the
    checked requests, what the engine generated for them and the
    ``Future.token_records`` it handed back beside."""
    cfg = model_config(config)
    model = cfg.serving_model()
    records = [model.read_records(r) for r in records]
    sliding = cfg.layer_types.index(window_moe.SLIDING)
    full = cfg.layer_types.index(window_moe.FULL)
    ring = (-(-cfg.sliding_window // block_size) + 1) * block_size
    # the request the attention part runs on: the first that wraps the
    # ring (else the longest)
    long = next((i for i, p in enumerate(prompts) if len(p) > ring),
                int(np.argmax([len(p) for p in prompts])))
    ok = True
    first_layers = att = None
    for i, (prompt, out, record) in enumerate(zip(prompts, outs, records)):
        readings, layers, *rest = forced_readings(
            config, weights, prompt, out, record,
            want_layers=(sliding, full) if i == long else ())
        good = within_limits(readings)
        log(dict(_worst(readings), check="generated_tokens_vs_reference",
                 prompt_len=len(prompt), tokens=out.tolist(),
                 logit_gaps=readings["gap"].tolist(),
                 value_errors=readings["value"].tolist(),
                 pick_distances=readings["pick_distance"].tolist(),
                 limits=[LOGIT_TOLERANCE, VALUE_TOLERANCE, PICK_DELTA],
                 ok=good))
        ok = ok and good
        first_layers = first_layers or layers
        if rest:
            att = rest[0]

    # the control and the whole-forward mutants, through the same
    # comparison on the first request's records: each has to fail it
    caught = {}
    for fault in (reference.CONTROL,) + reference.WHOLE_MUTANTS:
        readings, _ = forced_readings(config, weights, prompts[0], outs[0],
                                      records[0], fault)
        caught[fault] = not within_limits(readings)
        log(dict(_worst(readings), caught=caught[fault],
                 **({"check": "control", "control": fault}
                    if fault == reference.CONTROL
                    else {"check": "mutant", "mutant": fault})))

    # free, for the log: how often the reference alone parts ways
    readings, _ = forced_readings(config, weights, prompts[0], outs[0],
                                  records[0], force=False)
    log(dict(_worst(readings), check="free_run", rows=len(outs[0]),
             rows_picks_differ=int((readings["pick_distance"] > 0).sum())))

    # both kinds of layer's attention as this backend runs it
    p = len(prompts[long])
    for layer, faults in ((sliding, reference.ATTENTION_MUTANTS),
                          (full, ("rope_on_full",))):
        kind = layer == sliding
        program, ring = program_attention(
            config, weights, layer, att[layer]["input"], p, block_size)
        reading = attention_readings(config, att[layer], p, ring, program,
                                     sliding=kind)
        good = bool(
            (reading["attention_error"] <= ATTENTION_TOLERANCE).all())
        log(dict(_worst(reading), check="program_attention", layer=layer,
                 kind=cfg.layer_types[layer], rows=len(program),
                 prompt_len=p, ring=ring, limit=ATTENTION_TOLERANCE,
                 ok=good))
        ok = ok and good
        for mutant in faults:
            reading = attention_readings(config, att[layer], p, ring,
                                         program, mutant, kind)
            by_rows = not bool(
                (reading["attention_error"] <= ATTENTION_TOLERANCE).all())
            caught[mutant] = caught.get(mutant, False) or by_rows
            log(dict(_worst(reading), check="mutant", mutant=mutant,
                     part="attention", prompt_len=p, caught=by_rows))

    # the router and the routed sum, in the first and last expert layer
    picked = [(config["num_dense_layers"], first_layers[0]["input"]),
              (config["num_hidden_layers"] - 1, first_layers[-1]["input"])]
    for layer, x in picked:
        good, reading = parts_against_reference(config, weights, layer, x)
        log(dict(reading, check="program_router_and_experts", ok=good))
        ok = ok and good
    passed, reading = parts_against_reference(
        config, weights, *picked[0], "no_route_scale")
    log(dict(reading, check="mutant", mutant="no_route_scale",
             part="router", caught=not passed))
    caught["no_route_scale"] = caught["no_route_scale"] or not passed
    # the control of this part: the held experts in 8 bits have to fail
    # the routed sum's own limit
    _, reading = parts_against_reference(config, weights, *picked[0],
                                         reference.CONTROL)
    caught["experts_8bit"] = not reading["routed_error"] <= EXPERT_TOLERANCE
    log(dict(reading, check="control", control=reference.CONTROL,
             part="experts", limit=EXPERT_TOLERANCE,
             caught=caught["experts_8bit"]))
    return ok and all(caught.values())
