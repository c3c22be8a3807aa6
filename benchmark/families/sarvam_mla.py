"""``sarvam_mla`` family (latent attention, routed and shared experts):
from a configuration file to what the serving driver runs. Serving
only: the program has no training graph for these blocks yet
(``ROADMAP.md``).

A configuration of this family is ONE CHIP'S SHARE of a deployment: its
``deployment`` group says which routed experts and which rows of the
vocabulary this chip holds; the file's own ``num_experts`` and
``vocab_size`` are the held ones, the router stays as wide as the
published model.

Offers the drivers ``build_engine``, ``engine_reference_logits``,
``LOGIT_TOLERANCE`` and ``check_generated`` (this family's own account
of ``correct``; see there), and ``train_flops_per_token``, which
refuses.

**What ``correct`` holds the engine to.** The engine returns tokens,
and routing is discrete: a bfloat16 activation moves a router score by
a few thousandths, and where the eighth and ninth scores of a token lie
closer than that the engine and a float32 reference pick different
experts, which moves that token's logits by far more than rounding
does. A tolerance wide enough to cover such rows would let real faults
through on all the others. So the engine hands back, beside every
generated token, the RECORD of the row that decided it
(``Future.token_records``: each expert layer's picks and the row's
best logit, computed by the timed programs themselves), and the
reference runs FORCED onto those picks (teacher-forced on the prompt
and the engine's earlier tokens, as the GPT family checks). Every
checked row is then held to the same three limits:

1. the chosen token's logit lies within ``LOGIT_TOLERANCE`` of the
   forced reference's best;
2. the engine's own best logit lies within ``VALUE_TOLERANCE`` of the
   forced reference's logit for that token: rounding is on every row,
   so this reads the precision of everything the engine's programs
   and prepared parameters did for the row;
3. in every expert layer the engine's picks are the reference's own
   (on what are now the same inputs) or lie within ``PICK_DELTA`` of
   them in ``p + b``: the largest score the engine passed over less
   the smallest it took instead.

The limits lie between the largest reading of the sound engine and the
smallest of the CONTROL, the reference with every matrix rounded to 8
bits (the nearest precision under the bfloat16 the configuration
states), run through this same comparison on the same records: it has
to fail, and the run logs its readings.

*Per-layer extras*, on the reference's inputs of the first checked
request, in the first and the last expert layer: ``ops/moe.py:route``
as the chip runs it against the reference's router on identical rows
(picks equal wherever the margin is over ``ROUTER_MARGIN``, weights to
``ROUTER_TOLERANCE``), and the grouped matmul over the held experts
against the reference's dense loop (relative RMS within
``EXPERT_TOLERANCE``). They hold the program's router to float32 and
the bias to the selection alone, which no logit can show.

Each of the reference's ``MUTANTS`` is run through the part that
answers for it and logged with its reading: a part that passes a mutant
fails the run. One free run of the reference (first request) is logged
beside, with the share of rows on which its picks and the engine's
part ways.
"""
import numpy as np

from benchmark.harness.session import executor_seed
from benchmark.reference import sarvam_mla as reference

# Each limit lies between the sound engine's largest reading and the
# 8-bit control's smallest (my chip runs, PR 32: 4 runs, 4 seeds, 512
# checked rows; the control on 32 rows a run), about the geometric mean:
# chosen token under the forced best: 0.053 | control 0.66
LOGIT_TOLERANCE = 0.2
# engine's best logit against the forced reference's: 0.085 | 0.42
VALUE_TOLERANCE = 0.2
# the engine's picks from the reference's own, in p + b: 0.0083 | 0.093
PICK_DELTA = 0.03
# the per-layer extras: the program's expert layer reads 0.0034-0.0035
# from the reference's, 8-bit expert weights 0.057-0.059; router
# weights 0.0 against 0.0029-0.0036 with bfloat16 routing
ROUTER_MARGIN = 1e-4
ROUTER_TOLERANCE = 1e-3     # relative, on a pick's weight
EXPERT_TOLERANCE = 0.015    # relative RMS of the routed sum


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
        num_routed_experts=d["num_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["num_shared_experts"],
        first_k_dense_replace=config["first_k_dense_replace"],
        experts_held=(d["experts_first"], config["num_experts"]),
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=config["serve_dtype"])


def train_flops_per_token(config, seq_len):
    raise NotImplementedError(
        "the sarvam_mla family is serving only: the program has no "
        "training graph for latent attention or expert layers")


def seeded_weights(config, seed):
    """Every serving parameter, made on the device from the seed, one
    jitted call a parameter (an expert stack is 1.07e9 elements: its
    float32 draw is freed before the next is made): N(0,
    ``initializer_std``) matrices rounded to the serving dtype, unit
    norms, router weights of the same draw held in float32, router bias
    N(0, ``router_bias_std``) so that selection and weight differ. The
    hardware generator (``rbg``) draws them: threefry takes minutes for
    5.5e9 elements."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.latent_moe import latent_moe_param_shapes

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

    shapes = latent_moe_param_shapes(model_config(config))
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
    """[len(positions), V] float32 logits of the plain forward over
    ``tokens`` at ``positions``."""
    return reference.logits_at(weights, config, tokens, positions, pad_to)


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def _pad(n):
    """One compiled reference per 1024 tokens of length."""
    return -(-n // 1024) * 1024


def pick_distance(scores, picks):
    """How far the given ``picks [n, k]`` lie from the top ``k`` of
    ``scores [n, E]``, a row: the largest score passed over less the
    smallest taken in its place; 0 where the sets are equal, infinite
    where ``picks`` are not ``k`` different experts."""
    k = picks.shape[1]
    own = np.argsort(-scores, axis=1)[:, :k]
    out = np.zeros(len(scores))
    for r in range(len(scores)):
        missed = np.setdiff1d(own[r], picks[r])
        extra = np.setdiff1d(picks[r], own[r])
        if len(missed) != len(extra) or len(set(picks[r])) != k:
            out[r] = np.inf
        elif len(extra):
            out[r] = scores[r, missed].max() - scores[r, extra].min()
    return out


def forced_readings(config, weights, prompt, out, record, mutant=None,
                    force=True):
    """One checked request against the reference forced onto the
    engine's picks (``force=False``: running free): per generated
    token, the chosen token's ``gap`` under the reference's best, the
    ``value`` error of the engine's own best logit, and the
    ``pick_distance`` of the engine's picks, the largest over the
    expert layers; and what the reference's layers saw."""
    p, new = len(prompt), len(out)
    rows = np.arange(p - 1, p - 1 + new)
    tokens = np.concatenate([prompt, out[:-1]])
    logits, layers = reference.forward(
        weights, config, tokens, rows, _pad(len(tokens)), mutant,
        record["router_picks"] if force else None)
    chosen = logits[np.arange(new), out]
    distance = np.max([pick_distance(layer["scores"],
                                     record["router_picks"][:, i])
                       for i, layer in enumerate(layers)], axis=0)
    return {"gap": logits.max(axis=-1) - chosen,
            "value": np.abs(record["best_logit"] - chosen),
            "pick_distance": distance}, layers


def within_limits(readings):
    return bool((readings["gap"] <= LOGIT_TOLERANCE).all()
                and (readings["value"] <= VALUE_TOLERANCE).all()
                and (readings["pick_distance"] <= PICK_DELTA).all())


def _worst(readings):
    return {"worst_" + k: float(np.max(v)) for k, v in readings.items()}


def program_parts(config, weights, layer, x):
    """The PROGRAM's router and expert layer (``hetu_tpu/ops/moe.py``,
    as this backend runs them: the grouped-matmul kernel on a TPU) on
    rows ``x [n, hidden]``: ``(experts, weights, routed sum)``."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import moe
    cfg = model_config(config)
    w = reference.layer_weights(weights, layer)

    def parts(x, w):
        experts, wts, _ = moe.route(
            x, w["router"], w["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
        routed, _ = moe.held_experts(
            x, experts, wts, jnp.ones(x.shape[0], bool),
            w["experts_gate_up"], w["experts_down"],
            first=cfg.experts_held[0])
        return experts, wts, routed

    return [np.asarray(a) for a in jax.jit(parts)(
        jnp.asarray(x, jnp.dtype(config["serve_dtype"])), w)]


def parts_against_reference(config, weights, layer, x, mutant=None):
    """Readings of parts 2 and 3 on rows ``x``: the program's router
    and expert layer against the reference's (or a mutant of it) on the
    same rows. ``x`` is rounded to the serving dtype first, so both see
    identical numbers."""
    import jax.numpy as jnp
    x = np.asarray(jnp.asarray(x, jnp.dtype(config["serve_dtype"]))
                   .astype(jnp.float32))
    experts, wts, routed = program_parts(config, weights, layer, x)
    r_experts, r_wts, r_margin, r_routed = reference.expert_layer_parts(
        weights, config, layer, x, mutant)
    k = r_experts.shape[1]      # a mutant may pick one fewer
    clear = r_margin > ROUTER_MARGIN
    same = (np.sort(experts[:, :k], axis=1)
            == np.sort(r_experts, axis=1)).all(axis=1) \
        & (experts.shape[1] == k)
    # weights by expert id, so that order among the picks does not matter
    wide = np.zeros((len(x), config["deployment"]["num_routed_experts"]))
    r_wide = wide.copy()
    np.put_along_axis(wide, experts, wts, axis=1)
    np.put_along_axis(r_wide, r_experts, r_wts, axis=1)
    agree = same & clear
    weight_error = float(np.max(
        np.abs(wide - r_wide)[agree] / np.maximum(r_wide[agree], 1e-6)
        * (r_wide[agree] > 0), initial=0.0))
    routed_error = float(
        np.sqrt(np.mean(np.square(routed[agree] - r_routed[agree])))
        / np.sqrt(np.mean(np.square(r_routed[agree])))) \
        if agree.any() else float("nan")
    reading = {"layer": layer, "rows": int(len(x)),
               "rows_clear": int(clear.sum()),
               "picks_differ_on_clear_rows": int((clear & ~same).sum()),
               "weight_error": weight_error,
               "routed_error": routed_error}
    ok = (reading["picks_differ_on_clear_rows"] == 0
          and weight_error <= ROUTER_TOLERANCE
          and routed_error <= EXPERT_TOLERANCE)
    return ok, reading


def check_generated(config, weights, prompts, outs, records, log):
    """``correct`` of this family (the module docstring says what it
    holds the engine to). ``prompts`` / ``outs`` / ``records``: the
    checked requests, what the engine generated for them and the
    ``Future.token_records`` it handed back beside."""
    model = model_config(config).serving_model()
    records = [model.read_records(r) for r in records]
    ok = True
    first_layers = None
    for prompt, out, record in zip(prompts, outs, records):
        readings, layers = forced_readings(config, weights, prompt, out,
                                           record)
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

    # the control and the whole-forward mutants, through the same
    # comparison on the first request's records: each has to fail it
    caught = {}
    for fault in (reference.CONTROL, "no_rope"):
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
    log(dict(_worst(readings), check="free_run",
             rows=len(outs[0]),
             rows_picks_differ=int((readings["pick_distance"] > 0).sum())))

    # per-layer extras, on the first checked request's rows, in the
    # first and the last expert layer
    first_moe = config["first_k_dense_replace"]
    picked = [(first_moe, first_layers[0]["input"]),
              (config["num_hidden_layers"] - 1, first_layers[-1]["input"])]
    for layer, x in picked:
        good, reading = parts_against_reference(config, weights, layer, x)
        log(dict(reading, check="program_router_and_experts", ok=good))
        ok = ok and good
    layer, x = picked[0]
    for mutant in reference.MUTANTS:
        if mutant in caught:
            continue
        passed, reading = parts_against_reference(
            config, weights, layer, x, mutant)
        caught[mutant] = not passed
        log(dict(reading, check="mutant", mutant=mutant,
                 caught=caught[mutant]))
    return ok and all(caught.values())
