"""``xing_mhc`` family (latent attention with a query LoRA, routed and
shared experts, ``hc_mult`` residual streams mixed by learned,
Sinkhorn-projected maps): from a configuration file to what the serving
driver runs. Serving only, as ``families/sarvam_mla.py`` is, and built
on it: the same engine, cache, driver and model class
(``hetu_tpu/models/latent_moe.py``) with the query form and the
residual form as configuration.

A configuration of this family is ONE PIPELINE STAGE of a deployment
whose layers each live whole on a chip: every routed expert and the
whole vocabulary are held here, and what is cut is depth alone.

Offers the drivers ``build_engine``, ``engine_reference_logits`` and
``check_generated``. **What ``correct`` holds the engine to** is what
``families/sarvam_mla.py``'s docstring sets out — the reference
(``reference/xing_mhc.py``) FORCED onto the routing the engine reports
beside its tokens, every checked row held to three limits, the 8-bit
control having to fail — with the limits placed again on THIS
configuration (below), and one more part:

*The residual path*, on the reference's own inputs of the first
checked request, in the feed-forward sublayer of the first and the last
held layer: ``ops/mhc.py`` as this backend runs it (the two kernels on
a TPU) against the reference's maps on identical rows. The rows and
columns of the program's ``Hres`` sum to 1 within ``SINKHORN_SUM``; its
``Hpost`` / ``Hres`` lie within ``MAP_TOLERANCE`` of the reference's;
``u`` and ``X'`` (both rounded to the serving dtype, as the stream is
held) within ``STREAM_TOLERANCE`` relative RMS. Maps computed in
bfloat16 move nearly every rounding of ``X'`` and fail the last.

Each of the reference's ``MUTANTS`` is run through the part that
answers for it and logged with its reading: a part that passes a mutant
fails the run (the two faults of the exit that the final norm hides,
``reference.BEHIND_THE_NORM``, are run and logged beside them). The router's and the experts' are
``families/sarvam_mla.py``'s own checks, run on this configuration
through the keys its file shares with that family's (``aliases``).
"""
import numpy as np

from benchmark.families import sarvam_mla as base
from benchmark.harness.session import executor_seed
from benchmark.reference import xing_mhc as reference
# what the parent lacks: it fails the cell here, in seconds
from hetu_tpu.ops import mhc

# Each limit lies between the sound engine's largest reading and the
# 8-bit control's smallest on THIS configuration (my chip runs, PR 39:
# 18 runs, 18 seeds, 2,304 checked rows; the control on 32 rows a run),
# about the geometric mean (0.16 / 0.20 / 0.030): the numbers the
# sarvam configuration's own readings gave, from these readings:
# chosen token under the forced best: 0.069 | control 0.378
LOGIT_TOLERANCE = 0.2
# engine's best logit against the forced reference's: 0.084 | 0.471
VALUE_TOLERANCE = 0.2
# the engine's picks from the reference's own, in p + b: 0.0112 | 0.080
PICK_DELTA = 0.03
# the residual path's extras (the same runs, 32 rows of two layers):
# |row or column sum of Hres - 1|: 5.7e-5 at hc_res_diag 2, under
# 1.4e-5 over 4,000 simulated draws at the file's 1; one Sinkhorn
# iteration for twenty reads 1e-2 and more
SINKHORN_SUM = 1e-4
# |Hpost, Hres - the reference's|: 1.8e-7 | one iteration 0.035,
# columns only 0.016, Hpost without its 2 0.47
MAP_TOLERANCE = 1e-4
# relative RMS of u and of X' (both rounded to bfloat16, as held):
# 3.1e-5 | maps rounded to bfloat16 2e-3 (tests/test_mhc.py), one
# iteration 0.042, columns only 0.017, softmax for sigmoid 0.74 (u)
STREAM_TOLERANCE = 5e-4
# b_res of the clamp's run: where the clamp decides the map
CLAMP_BIAS = 100.0


def model_config(config):
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
        num_routed_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["n_shared_experts"],
        first_k_dense_replace=config["first_k_dense_replace"],
        experts_held=(d["experts_first"], d["experts_held"]),
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=config["serve_dtype"],
        q_lora_rank=config["q_lora_rank"], qk_norm=False,
        hyper_connections={
            "streams": config["hc_mult"],
            "sinkhorn_iters": config["hc_sinkhorn_iters"],
            "eps": config["hc_eps"],
            "clamp": (config["mhc_h_res_clamp_min"],
                      config["mhc_h_res_clamp_max"])})


def train_flops_per_token(config, seq_len):
    raise NotImplementedError(
        "the xing_mhc family is serving only: the program has no "
        "training graph for latent attention, expert layers or "
        "hyper-connections")


def seeded_weights(config, seed):
    """Every serving parameter, made on the device from the seed, one
    jitted call a parameter, as ``families/sarvam_mla.py`` makes them;
    the hyper-connections' maps as the file's ``assumed.hc_init``
    says."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.latent_moe import latent_moe_param_shapes

    a = config["assumed"]
    n = config["hc_mult"]
    dtype = jnp.dtype(config["serve_dtype"])
    key = jax.random.key(executor_seed(seed), impl="rbg")
    makers = {}

    def draw(shape, kind):
        def normal(k, std):
            return std * jax.random.normal(k, shape, jnp.float32)

        if kind == "norm":
            return lambda k: jnp.ones(shape, jnp.float32)
        if kind == "router_bias":
            return lambda k: normal(k, a["router_bias_std"])
        if kind == "hc_phi":
            return lambda k: normal(k, a["hc_phi_std"])
        if kind == "hc_scale":
            return lambda k: jnp.full(shape, a["hc_scale_init"],
                                      jnp.float32)
        if kind == "hc_bias":
            diag = jnp.concatenate([
                jnp.zeros(2 * n),
                a["hc_res_diag"] * jnp.eye(n).reshape(-1)])
            return lambda k: diag + normal(k, a["hc_bias_std"])
        out = jnp.float32 if kind == "router" else dtype
        return lambda k: normal(k, a["initializer_std"]).astype(
            dtype).astype(out)

    def make(shape, kind):
        if (shape, kind) not in makers:
            makers[shape, kind] = jax.jit(draw(shape, kind))
        return makers[shape, kind]

    shapes = latent_moe_param_shapes(model_config(config))
    return {name: make(shape, kind)(jax.random.fold_in(key, i))
            for i, (name, (shape, kind)) in enumerate(sorted(
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

def forced_readings(config, weights, prompt, out, record, mutant=None,
                    force=True):
    """``families/sarvam_mla.py:forced_readings`` against THIS family's
    reference; returns ``(readings, the expert layers' views, every
    layer's residual view)``."""
    p, new = len(prompt), len(out)
    rows = np.arange(p - 1, p - 1 + new)
    tokens = np.concatenate([prompt, out[:-1]])
    logits, layers, streams = reference.forward(
        weights, config, tokens, rows, base._pad(len(tokens)), mutant,
        record["router_picks"] if force else None)
    chosen = logits[np.arange(new), out]
    distance = np.max([base.pick_distance(layer["scores"],
                                          record["router_picks"][:, i])
                       for i, layer in enumerate(layers)], axis=0)
    return {"gap": logits.max(axis=-1) - chosen,
            "value": np.abs(record["best_logit"] - chosen),
            "pick_distance": distance}, layers, streams


def within_limits(readings):
    return bool((readings["gap"] <= LOGIT_TOLERANCE).all()
                and (readings["value"] <= VALUE_TOLERANCE).all()
                and (readings["pick_distance"] <= PICK_DELTA).all())


def program_residual(config, weights, layer, streams, y, bias=None):
    """The PROGRAM's residual path (``hetu_tpu/ops/mhc.py``, as this
    backend runs it) around layer ``layer``'s feed-forward sublayer on
    rows ``streams [n, hc_mult, hidden]`` / ``y [n, hidden]``: ``(u,
    Hpost, Hres, X')`` as float32 numpy."""
    import jax
    import jax.numpy as jnp
    cfg = model_config(config)
    hc = cfg.hyper_connections
    dtype = jnp.dtype(config["serve_dtype"])
    phi, scale, own = reference.sublayer_maps(
        reference.layer_weights(weights, layer), "ffn")
    maps = {"phi": phi, "scale": scale,
            "bias": own if bias is None else jnp.asarray(bias, jnp.float32)}
    if mhc.wants_prepared(hc["streams"], cfg.hidden_size, dtype):
        maps["kernel"] = mhc.prepare(**maps)

    def parts(x, y, maps):
        u, carry = mhc.mhc_pre(x, maps, hc["sinkhorn_iters"], hc["eps"],
                               hc["clamp"])
        return u, carry, mhc.mhc_post(x, y, carry)

    u, carry, out = jax.jit(parts)(
        jnp.asarray(streams, dtype), jnp.asarray(y, dtype), maps)
    n = hc["streams"]
    if isinstance(carry, tuple):
        post, res = (np.asarray(a) for a in carry)
    else:       # the kernels' carry: maps's columns, a row a token
        carry = np.asarray(carry)
        post = carry[:, n:2 * n]
        res = carry[:, 2 * n:2 * n + n * n].reshape(-1, n, n)
    return (np.asarray(u.astype(jnp.float32)), post, res,
            np.asarray(out.astype(jnp.float32)))


def _relative_rms(got, want):
    return float(np.sqrt(np.mean(np.square(got - want)))
                 / np.sqrt(np.mean(np.square(want))))


def residual_against_reference(config, weights, layer, view, mutant=None,
                               bias=None):
    """Readings of the residual part on one layer's rows: the
    program's ``ops/mhc.py`` against the reference's (or a mutant of
    it) on the same rows, rounded to the serving dtype first so that
    both see identical numbers."""
    import jax.numpy as jnp
    dtype = jnp.dtype(config["serve_dtype"])

    def rounded(a):
        return np.asarray(jnp.asarray(a, dtype).astype(jnp.float32))

    streams, y = rounded(view["streams"]), rounded(view["y"])
    u, post, res, out = program_residual(config, weights, layer, streams,
                                         y, bias)
    r_u, r_post, r_res, r_out = reference.residual_parts(
        weights, config, layer, streams, y, mutant, bias)
    reading = {
        "layer": layer, "rows": int(len(streams)),
        # numpy's max, which keeps a reading that is not a number
        "hres_sum_error": float(np.max([np.abs(res.sum(axis=1) - 1).max(),
                                        np.abs(res.sum(axis=2) - 1).max()])),
        "map_error": float(np.max([np.abs(post - r_post).max(),
                                   np.abs(res - r_res).max()])),
        "u_error": _relative_rms(u, r_u),
        "stream_error": _relative_rms(out, r_out)}
    # ``not (a <= b)``: a reading that is not a number fails
    ok = (reading["hres_sum_error"] <= SINKHORN_SUM
          and reading["map_error"] <= MAP_TOLERANCE
          and reading["u_error"] <= STREAM_TOLERANCE
          and reading["stream_error"] <= STREAM_TOLERANCE)
    return bool(ok), reading


def check_generated(config, weights, prompts, outs, records, log):
    """``correct`` of this family (the module docstring says what it
    holds the engine to)."""
    model = model_config(config).serving_model()
    records = [model.read_records(r) for r in records]
    ok = True
    first = None
    for prompt, out, record in zip(prompts, outs, records):
        readings, layers, streams = forced_readings(
            config, weights, prompt, out, record)
        good = within_limits(readings)
        log(dict(base._worst(readings),
                 check="generated_tokens_vs_reference",
                 prompt_len=len(prompt), tokens=out.tolist(),
                 logit_gaps=readings["gap"].tolist(),
                 value_errors=readings["value"].tolist(),
                 pick_distances=readings["pick_distance"].tolist(),
                 limits=[LOGIT_TOLERANCE, VALUE_TOLERANCE, PICK_DELTA],
                 ok=good))
        ok = ok and good
        first = first or (layers, streams)
    layers, streams = first

    # the control and the whole-forward mutants, through the same
    # comparison on the first request's records: each has to fail it
    caught = {}
    for fault in (reference.CONTROL, "no_rope") + reference.BEHIND_THE_NORM:
        readings, _, _ = forced_readings(config, weights, prompts[0],
                                         outs[0], records[0], fault)
        failed = not within_limits(readings)
        if fault in reference.BEHIND_THE_NORM:    # logged, not counted
            log(dict(base._worst(readings), check="behind_the_final_norm",
                     mutant=fault, caught=failed))
            continue
        caught[fault] = failed
        log(dict(base._worst(readings), caught=failed,
                 **({"check": "control", "control": fault}
                    if fault == reference.CONTROL
                    else {"check": "mutant", "mutant": fault})))

    readings, _, _ = forced_readings(config, weights, prompts[0], outs[0],
                                     records[0], force=False)
    log(dict(base._worst(readings), check="free_run", rows=len(outs[0]),
             rows_picks_differ=int((readings["pick_distance"] > 0).sum())))

    # the router and the experts: the sarvam family's own part, in the
    # first and the last expert layer
    first_moe = config["first_k_dense_replace"]
    last = config["num_hidden_layers"] - 1
    picked = [(first_moe, layers[0]["input"]), (last, layers[-1]["input"])]
    for layer, x in picked:
        good, reading = base.parts_against_reference(config, weights,
                                                     layer, x)
        log(dict(reading, check="program_router_and_experts", ok=good))
        ok = ok and good
    layer, x = picked[0]
    for mutant in reference.base.MUTANTS:
        if mutant in caught:
            continue
        passed, reading = base.parts_against_reference(
            config, weights, layer, x, mutant)
        caught[mutant] = not passed
        log(dict(reading, check="mutant", mutant=mutant,
                 caught=caught[mutant]))

    # the residual path: ops/mhc.py in the first and the last held layer
    for layer in (0, last):
        good, reading = residual_against_reference(
            config, weights, layer, streams[layer])
        log(dict(reading, check="program_residual_path", ok=good))
        ok = ok and good
    n = config["hc_mult"]
    large = np.concatenate([np.zeros(2 * n), CLAMP_BIAS * (
        2 * np.eye(n) - 1).reshape(-1)]).astype(np.float32)
    good, reading = residual_against_reference(
        config, weights, last, streams[last], bias=large)
    log(dict(reading, check="program_residual_path", b_res=CLAMP_BIAS,
             ok=good))
    ok = ok and good
    for mutant in reference.MHC_MUTANTS:
        if mutant in caught:
            continue
        passed, reading = residual_against_reference(
            config, weights, last, streams[last], mutant,
            large if mutant == "no_clamp" else None)
        caught[mutant] = not passed
        log(dict(reading, check="mutant", mutant=mutant,
                 caught=caught[mutant]))
    return ok and all(caught.values())
