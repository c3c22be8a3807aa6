"""``ling_kda`` family (delta-rule linear-attention layers with a matrix
state beside one latent-attention layer a period, a gate a head on every
mixer, routed experts under a group-limited router and a shared expert):
from a configuration file to what the serving driver runs. Serving only:
the program has no graph op with a gradient for the delta rule
(``ROADMAP.md``).

A configuration of this family is ONE CHIP'S SHARE of a deployment, as
``families/sarvam_mla.py`` has it: ``deployment`` says which routed
experts and which rows of the vocabulary this chip holds; the file's own
``num_experts`` and ``vocab_size`` are the held ones, the router stays as
wide as the published model.

Offers the drivers ``build_engine``, ``engine_reference_logits`` and
``check_generated``. **What ``correct`` holds the engine to**, in two
parts and an extra:

*The timed path's tokens.* Every checked request's generated tokens were
produced by a prefill (the chunk kernel over a padded bucket, the state
left at the prompt's last real token in a slot, the latent rows in the
paged pool) and decode steps through slot and pool. Routing is discrete,
so the engine hands back, beside every generated token, the RECORD of the
row that decided it (``Future.token_records``: each expert layer's picks
and the row's best logit), and the reference (``reference/ling_kda.py``,
float32 at the highest precision, a scan a token) runs its full forward
over prompt + generated tokens FORCED onto those picks. Every checked row
is held to three limits: the chosen token's logit within
``LOGIT_TOLERANCE`` of the forced reference's best; the engine's own best
logit within ``VALUE_TOLERANCE`` of the forced reference's logit for that
token; the engine's picks within ``PICK_DELTA`` of the reference's own
under the GROUP LIMIT (``pick_readings``: in ``s + bias`` among the
experts of the groups kept, and in the groups' scores where the engine
kept another group). The 8-bit control (``all_8bit``) has to fail.

*The delta-rule mixer as this backend runs it* (``models/latent_moe.py``
over ``ops/kda.py``: the chunk and step kernels on a TPU), float32, on
the reference's own input to the first delta-rule layer for the first
checked request: a prefill over the prompt padded to its bucket, then one
step a generated token through a state slot, against the reference's
scan on the same rows; every row's relative error within
``MIXER_TOLERANCE`` and the stepped rows' median within
``MIXER_STEP_TOLERANCE``. Here the whole-model noise of bfloat16 matrices is
absent, so this part sees what the first cannot: a state kept in
bfloat16. It is tied to the timed engine through the slots: the ENGINE's
own state entry (``engine.cache.pools``, the engine ``build_engine`` made
and hands on with its weights) has to be what its model's
``state_layout()`` says, slot for slot, with the recurrent state in the
dtype the configuration states (``assumed.kda_state_dtype``), and this
part's slots are made by the engine's cache class from that same
``state_layout()`` (``state_entry``); the control ``state_bf16`` is THAT
entry holding what bfloat16 slots would (rounded at every write) under
the same program, which has to fail.

*The router and the routed sum as this backend runs them*
(``ops/moe.py``), on the reference's inputs to the first and the last
expert layer at the first request's checked rows: picks equal wherever
the margin (among experts and among groups) is over ``ROUTER_MARGIN``,
weights to ``ROUTER_TOLERANCE``, the routed sum to ``EXPERT_TOLERANCE``.

Each of the reference's ``MUTANTS`` is run through the part that answers
for it and logged with its reading: a part that passes a mutant fails the
run.
"""
import numpy as np

from benchmark.harness.session import executor_seed
from benchmark.reference import ling_kda as reference
# what the parent lacks: it fails the cell here, in seconds
from hetu_tpu.ops import kda as _kda  # noqa: F401

# Each limit lies between the sound engine's largest reading and the
# control's (or the mutant's) smallest, about their geometric mean (my
# chip runs, PR 54: 17 runs, 17 seeds, 68 checked requests of 32 rows;
# the runs and seeds are in PERF.md sections 4 and 6; the control and
# the mutants on the first request's 32 rows a run):
# chosen token under the forced best: 0.154 | control (all_8bit) 0.61
LOGIT_TOLERANCE = 0.4
# engine's best logit against the forced reference's: 0.172 | 0.65
VALUE_TOLERANCE = 0.3
# the engine's picks from the reference's own under the group limit, in
# s + bias (a group's score is a sum of two): 0.035 | 0.146; the latent
# layer a place early 0.158, the group limit ignored 0.082 (that fault
# is the router part's to catch: 24-29 of 32 rows pick otherwise)
PICK_DELTA = 0.06
# relative, a row of the mixer's output over a prompt and 31 steps
# (1,108-6,167 rows a run): 1.4e-4 - 8.2e-4 over 17 runs, the worst rows
# those where a head's output nearly cancels before its norm (the
# median row reads 4.5e-6) | the slots in bfloat16 under the same
# program (the prefill's state rounded into its slot, then every
# step's) 7.0e-3 - 7.6e-3 (four runs); every mixer mutant over 0.5
MIXER_TOLERANCE = 3e-3
# the same, the MEDIAN over the rows that went through a slot (the 31
# steps): 3.4e-6 - 4.0e-6 | the slots in bfloat16 4.9e-3 - 5.3e-3 (four
# runs; a worst row sits 2.3x over MIXER_TOLERANCE there, the stepped
# rows' median 49x over this: a bfloat16 slot costs EVERY step's row
# parts in a thousand, a cancelling head or none)
MIXER_STEP_TOLERANCE = 1e-4
# the routed sum (relative RMS): 0.0034-0.0035 | the held experts in 8
# bits 0.0580-0.0587; the router is float32 in the configuration, so its
# weights read 0.0-2.9e-7 and their limit is held against the group
# limit alone
ROUTER_MARGIN = 1e-4
ROUTER_TOLERANCE = 1e-3     # relative, on a pick's weight
EXPERT_TOLERANCE = 0.015    # relative RMS of the routed sum


def model_config(config, dtype=None):
    from hetu_tpu.models.latent_moe import LatentMoEConfig
    d = config["deployment"]
    return LatentMoEConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_routed_experts=d["num_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["num_shared_experts"],
        first_k_dense_replace=config["first_k_dense_replace"],
        experts_held=(d["experts_first"], config["num_experts"]),
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        rope_scaling=None,
        max_position_embeddings=config["max_position_embeddings"],
        dtype=dtype or config["serve_dtype"],
        q_lora_rank=config["q_lora_rank"],
        qk_norm=config["use_qk_norm"],
        layer_types=config["layer_types"],
        kda_head_dim=config["head_dim"],
        kda_conv_width=config["short_conv_kernel_size"],
        kda_lower_bound=config["kda_lower_bound"],
        attn_output_gate=config["assumed"]["attn_output_gate"],
        n_group=config["n_group"], topk_group=config["topk_group"])


def train_flops_per_token(config, seq_len):
    raise NotImplementedError(
        "the ling_kda family is serving only: the program has no training "
        "graph for the delta rule, latent attention or expert layers")


def seeded_weights(config, seed):
    """Every serving parameter, made on the device from the seed, one
    jitted call a (shape, kind), as the file's ``assumed.weights`` says
    (``families/sarvam_mla.py`` has the reasons for the generator)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.latent_moe import latent_moe_param_shapes

    a = config["assumed"]
    dtype = jnp.dtype(config["serve_dtype"])
    key = jax.random.key(executor_seed(seed), impl="rbg")
    makers = {}

    def draw(shape, kind):
        if kind == "norm":
            return lambda k: jnp.ones(shape, jnp.float32)
        if kind == "router_bias":
            return lambda k: a["router_bias_std"] * jax.random.normal(
                k, shape, jnp.float32)
        if kind == "conv":
            return lambda k: jax.random.uniform(k, shape, jnp.float32,
                                                -0.5, 0.5)
        if kind == "a_log":
            return lambda k: jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 0.5, 4.0))
        if kind == "dt_bias":
            return lambda k: jax.random.uniform(k, shape, jnp.float32,
                                                -6.0, 2.0)
        out = jnp.float32 if kind == "router" else dtype
        return lambda k: (a["initializer_std"] * jax.random.normal(
            k, shape, jnp.float32)).astype(dtype).astype(out)

    def make(shape, kind):
        if (shape, kind) not in makers:
            makers[shape, kind] = jax.jit(draw(shape, kind))
        return makers[shape, kind]

    shapes = latent_moe_param_shapes(model_config(config))
    return {name: make(shape, kind)(jax.random.fold_in(key, n))
            for n, (name, (shape, kind)) in enumerate(sorted(
                shapes.items()))}


class EngineWeights(dict):
    """The seeded parameters by name, and the ``engine`` that was built
    from them: ``check_generated`` gets the weights from the driver and
    holds that engine's slots to the configuration through them."""
    engine = None


def build_engine(config, engine_kw, seed):
    from hetu_tpu.serving.scheduler import ContinuousBatchingEngine
    weights = EngineWeights(seeded_weights(config, seed))
    weights.engine = ContinuousBatchingEngine(
        model_config(config), weights.__getitem__, **engine_kw)
    return weights.engine, weights


def engine_reference_logits(config, weights, tokens, positions,
                            pad_to=None):
    return reference.logits_at(weights, config, tokens, positions, pad_to)


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def group_scores(scores, n_group):
    """``[n, groups]``: the sum of each group's two largest scores."""
    grouped = scores.reshape(len(scores), n_group, -1)
    return np.sort(grouped, axis=-1)[..., -2:].sum(-1)


def limited_top_k(scores, k, n_group, topk_group):
    """A NumPy spelling of the group-limited selection on ``scores [n,
    E]`` (``s + bias``): ``(picks [n, k], the groups kept [n,
    topk_group])``."""
    kept = np.argsort(-group_scores(scores, n_group), axis=1,
                      kind="stable")[:, :topk_group]
    size = scores.shape[1] // n_group
    allowed = (kept[:, :, None] == (np.arange(scores.shape[1])
                                    // size)[None, None, :]).any(axis=1)
    masked = np.where(allowed, scores, -np.inf)
    return np.argsort(-masked, axis=1, kind="stable")[:, :k], kept


def pick_readings(scores, picks, config, limited=True):
    """How far the given ``picks [n, k]`` lie from the group-limited
    selection on ``scores [n, E]``, a row: the larger of (the score of
    the last group the router would keep less the least score of a group
    the picks lie in) and (among the experts of the groups then kept,
    the largest score passed over less the smallest taken in its place);
    0 where the picks are the router's own, infinite where they are not
    ``k`` different experts. ``limited=False``: no group limit."""
    n_group = config["n_group"] if limited else 1
    keep_n = config["topk_group"] if limited else 1
    k = picks.shape[1]
    size = scores.shape[1] // n_group
    groups = group_scores(scores, n_group)
    out = np.zeros(len(scores))
    for r in range(len(scores)):
        if len(set(picks[r])) != k:
            out[r] = np.inf
            continue
        theirs = np.unique(picks[r] // size)
        if len(theirs) > keep_n:
            out[r] = np.inf
            continue
        order = np.argsort(-groups[r], kind="stable")
        # the groups the picks lie in, filled up with the best others
        kept = list(theirs) + [g for g in order if g not in theirs]
        kept = np.asarray(kept[:keep_n])
        by_group = groups[r, order[keep_n - 1]] - groups[r, theirs].min()
        allowed = np.isin(np.arange(scores.shape[1]) // size, kept)
        masked = np.where(allowed, scores[r], -np.inf)
        own = np.argsort(-masked, kind="stable")[:k]
        missed = np.setdiff1d(own, picks[r])
        extra = np.setdiff1d(picks[r], own)
        by_expert = scores[r, missed].max() - scores[r, extra].min() \
            if len(extra) else 0.0
        out[r] = max(by_group, by_expert, 0.0)
    return out


def forced_readings(config, weights, prompt, out, record, mutant=None,
                    force=True, want_layer=None):
    """One checked request against the reference forced onto the engine's
    picks (``force=False``: running free): per generated token, the
    chosen token's ``gap`` under the reference's best, the ``value``
    error of the engine's own best logit, and the ``pick_distance`` of
    the engine's picks, the largest over the expert layers; and what the
    reference's layers saw."""
    p, new = len(prompt), len(out)
    rows = np.arange(p - 1, p - 1 + new)
    tokens = np.concatenate([prompt, out[:-1]])
    logits, layers, *rest = reference.forward(
        weights, config, tokens, rows, mutant,
        record["router_picks"] if force else None, want_layer=want_layer)
    chosen = logits[np.arange(new), out]
    distance = np.max([pick_readings(
        layer["scores"], record["router_picks"][:, i], config,
        limited=mutant != "group_limit_ignored")
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


def _bucket(p):
    """The engine's prompt bucket of a ``p``-token prompt (the power of
    two at or above it); for a prompt that fills its bucket, the next:
    the fault has to have padding to run over."""
    bucket = 1 << (p - 1).bit_length()
    return bucket if bucket > p else 2 * p


def layer_block(cfg, weights, layer):
    """Layer ``layer``'s parameters as the program holds them for the
    configuration ``cfg``: matrices in its dtype, the rest float32."""
    import jax.numpy as jnp
    from hetu_tpu.models.latent_moe import latent_moe_param_shapes
    p = f"lm_h{layer}_"
    dtype = jnp.dtype(cfg.dtype)
    return {k[len(p):]: jnp.asarray(
                weights[k], dtype if kind == "matrix" else jnp.float32)
            for k, (_, kind) in latent_moe_param_shapes(cfg).items()
            if k.startswith(p) and not k.startswith(p + "experts_")}


def state_entry(cfg, slots=1):
    """The state entry of ``slots`` sequences (and the scratch slot) as
    the ENGINE's cache makes it for the configuration ``cfg``: its class,
    its model's ``state_layout()``."""
    from hetu_tpu.serving.kvcache import PagedKVCache
    cache = PagedKVCache(cfg, num_blocks=1, state_slots=slots)
    return cache.pools[cfg.serving_model().pool_kinds.index("state")]


def engine_state_reading(config, engine):
    """Whether the timed engine's own state entry is what its model's
    ``state_layout()`` says (every array: slots + 1 of the stated shape,
    in the stated dtype) with the recurrent state in the configuration's
    ``assumed.kda_state_dtype``: ``(ok, reading)``."""
    if engine is None:
        return False, {"engine": None}
    model, cache = engine.model, engine.cache
    entry = cache.pools[model.pool_kinds.index("state")]
    want = {name: [[cache.state_slots + 1, *shape], str(np.dtype(dtype))]
            for name, shape, dtype in model.state_layout()}
    held = {name: [list(a.shape), str(np.dtype(a.dtype))]
            for name, a in entry.items()}
    stated = config["assumed"]["kda_state_dtype"]
    ok = held == want and held["kda"][1] == stated
    return ok, {"held": held, "state_layout": want, "stated": stated}


def program_mixer(config, weights, layer, u, p, slots_bf16=False):
    """The PROGRAM's delta-rule mixer of layer ``layer`` (float32) over
    the normed rows ``u [T, hidden]`` as the engine walks them: a prefill
    over the first ``p`` padded to their bucket, then a step a row
    through slot 1 of the state entry the engine's cache makes
    (``state_entry``). ``slots_bf16`` (the ``state_bf16`` control): what
    a slot holds is rounded to bfloat16 whenever it is written, the
    prefill's state and every step's, as slots of that dtype would hold
    it. Returns ``[T, hidden]`` float32 numpy."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import latent_moe
    cfg = model_config(config, "float32")
    blk = layer_block(cfg, weights, layer)
    at = cfg.layer_types[:layer].count(latent_moe.KDA)
    pool = state_entry(cfg)["kda"]

    def held(pool):
        # an explicit rounding: a cast there and back is one the
        # compiler may drop (excess precision is allowed)
        return jax.lax.reduce_precision(
            pool, exponent_bits=8, mantissa_bits=7) if slots_bf16 else pool
    bucket = 1 << (p - 1).bit_length()
    heads, d = cfg.num_attention_heads, cfg.kda_head_dim
    taps = cfg.kda_conv_width
    u = jnp.asarray(u, jnp.float32)
    padded = jnp.concatenate(
        [u[:p], jnp.broadcast_to(u[p - 1], (bucket - p, u.shape[1]))])

    def prefill(blk, rows):
        with jax.default_matmul_precision("highest"):
            return latent_moe.kda_prefill(
                cfg, blk, rows[None],
                jnp.zeros((1, taps - 1, 3 * heads * d)),
                jnp.zeros((1, heads, d, d)), jnp.asarray([p], jnp.int32))

    def step(blk, row, tail, pool):
        with jax.default_matmul_precision("highest"):
            out, tail, pool = latent_moe.kda_step(
                cfg, blk, row[None], tail, pool,
                jnp.asarray([1], jnp.int32), at)
            return out, tail, held(pool)

    out, tail, state = jax.jit(prefill)(blk, padded)
    outs = [np.asarray(out[0, :p])]
    pool = held(pool.at[1, at].set(state[0]))
    step = jax.jit(step, donate_argnums=(3,))
    for row in u[p:]:
        out, tail, pool = step(blk, row, tail, pool)
        outs.append(np.asarray(out))
    return np.concatenate(outs)


def mixer_readings(config, weights, layer, u, p, mutant=None,
                   program=None):
    """The second part's reading: the largest relative error of a row of
    the program's mixer output against the reference's (or a fault of
    it) on the same rows."""
    if mutant == "state_bf16":      # the program's fault, not the reference's
        program, mutant = program_mixer(config, weights, layer, u, p,
                                        slots_bf16=True), None
    elif program is None:
        program = program_mixer(config, weights, layer, u, p)
    if mutant == "state_at_bucket_end":
        bucket = _bucket(p)
        rows = np.concatenate([np.arange(p), np.arange(bucket, bucket
                                                       + len(u) - p)])
        u = np.concatenate([u[:p], np.broadcast_to(
            u[p - 1], (bucket - p, u.shape[1])), u[p:]])
    else:
        rows = np.arange(len(u))
    w = reference.layer_weights(weights, layer)
    state = None
    for _ in range(2 if mutant == "slot_not_zeroed" else 1):
        want, state = reference.kda_layer(
            w, np.asarray(u, np.float32), config, mutant, p, state)
    want = np.asarray(want)[rows]
    error = np.linalg.norm(program - want, axis=1) \
        / np.linalg.norm(want, axis=1)
    return {"mixer_error": error,
            "mixer_step_error": np.median(error[p:])}, program


def mixer_within_limits(reading):
    return bool((reading["mixer_error"] <= MIXER_TOLERANCE).all()
                and reading["mixer_step_error"] <= MIXER_STEP_TOLERANCE)


def program_parts(config, weights, layer, x):
    """The PROGRAM's router and expert layer (``hetu_tpu/ops/moe.py``, as
    this backend runs them) on rows ``x [n, hidden]``: ``(experts,
    weights, routed sum)``."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import moe
    cfg = model_config(config)
    w = reference.layer_weights(weights, layer)

    def parts(x, w):
        experts, wts, _ = moe.route(
            x, w["router"], w["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.n_group, cfg.topk_group)
        routed, _ = moe.held_experts(
            x, experts, wts, jnp.ones(x.shape[0], bool),
            w["experts_gate_up"], w["experts_down"],
            first=cfg.experts_held[0])
        return experts, wts, routed

    return [np.asarray(a) for a in jax.jit(parts)(
        jnp.asarray(x, jnp.dtype(config["serve_dtype"])),
        {k: w[k] for k in ("router", "router_bias", "experts_gate_up",
                           "experts_down")})]


def parts_against_reference(config, weights, layer, x, mutant=None):
    """Readings of the extra on rows ``x`` (rounded to the serving dtype
    first, so both see identical numbers)."""
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


def check_generated(config, weights, prompts, outs, records, log):
    """``correct`` of this family (the module docstring says what it
    holds the engine to)."""
    model = model_config(config).serving_model()
    records = [model.read_records(r) for r in records]
    kda_layer = config["layer_types"].index(reference.KDA)
    ok = True
    first_layers = u = None
    for i, (prompt, out, record) in enumerate(zip(prompts, outs, records)):
        readings, layers, *rest = forced_readings(
            config, weights, prompt, out, record,
            want_layer=kda_layer if i == 0 else None)
        good = within_limits(readings)
        log(dict(_worst(readings), check="generated_tokens_vs_reference",
                 prompt_len=len(prompt), tokens=out.tolist(),
                 logit_gaps=readings["gap"].tolist(),
                 value_errors=readings["value"].tolist(),
                 pick_distances=readings["pick_distance"].tolist(),
                 limits=[LOGIT_TOLERANCE, VALUE_TOLERANCE, PICK_DELTA],
                 ok=good))
        ok = ok and good
        if i == 0:
            first_layers, u = layers, rest[0]

    # the control and the whole-forward mutants, through the same
    # comparison on the first request's records: each has to fail it
    prompt, out, record = prompts[0], outs[0], records[0]
    caught = {}
    for fault in ("all_8bit",) + reference.WHOLE_MUTANTS:
        readings, _ = forced_readings(config, weights, prompt, out, record,
                                      fault)
        caught[fault] = not within_limits(readings)
        log(dict(_worst(readings), caught=caught[fault], fault=fault,
                 check="control" if fault in reference.CONTROLS
                 else "mutant", part="logits"))

    # free, for the log: how often the reference alone parts ways
    readings, _ = forced_readings(config, weights, prompt, out, record,
                                  force=False)
    log(dict(_worst(readings), check="free_run", rows=len(out),
             rows_picks_differ=int((readings["pick_distance"] > 0).sum())))

    # the timed engine's slots are what the configuration states, and
    # the delta-rule mixer as this backend runs it through slots made the
    # same way, on the first request's rows of the first such layer
    good, reading = engine_state_reading(
        config, getattr(weights, "engine", None))
    log(dict(reading, check="engine_state_entry", ok=good))
    ok = ok and good
    p = len(prompt)
    reading, program = mixer_readings(config, weights, kda_layer, u, p)
    good = mixer_within_limits(reading)
    log(dict(_worst(reading), check="program_mixer", layer=kda_layer,
             rows=len(u), prompt_len=p,
             limits=[MIXER_TOLERANCE, MIXER_STEP_TOLERANCE], ok=good))
    ok = ok and good
    for fault in ("state_bf16",) + reference.MIXER_MUTANTS:
        reading, _ = mixer_readings(config, weights, kda_layer, u, p, fault,
                                    program)
        caught[fault] = not mixer_within_limits(reading)
        log(dict(_worst(reading), caught=caught[fault], fault=fault,
                 check="control" if fault in reference.CONTROLS
                 else "mutant", part="mixer"))

    # the router and the routed sum, in the first and last expert layer
    dense = config["first_k_dense_replace"]
    picked = [(dense, first_layers[0]["input"]),
              (config["num_hidden_layers"] - 1, first_layers[-1]["input"])]
    for layer, x in picked:
        good, reading = parts_against_reference(config, weights, layer, x)
        log(dict(reading, check="program_router_and_experts", ok=good))
        ok = ok and good
    passed, reading = parts_against_reference(
        config, weights, *picked[0], "group_limit_ignored")
    log(dict(reading, check="mutant", fault="group_limit_ignored",
             part="router", caught=not passed))
    caught["group_limit_ignored"] = caught["group_limit_ignored"] \
        or not passed
    _, reading = parts_against_reference(config, weights, *picked[0],
                                         "experts_8bit")
    caught["experts_8bit"] = not reading["routed_error"] <= EXPERT_TOLERANCE
    log(dict(reading, check="control", fault="experts_8bit", part="experts",
             limit=EXPERT_TOLERANCE, caught=caught["experts_8bit"]))
    return ok and all(caught.values())
