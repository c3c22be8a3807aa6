"""``jamba_ssm`` family (Mamba-1 state-space layers with RMS norms on
``dt`` / ``B`` / ``C``, every ``attn_layer_period``-th layer multi-query
attention without positions, dense SwiGLU, tied head): from a
configuration file to what the serving driver runs. Serving only: the
program has no graph op with a gradient for a scan.

Offers the drivers ``build_engine``, ``engine_reference_logits`` and
``check_generated``. **What ``correct`` holds the engine to**, in two
parts:

*The timed path's tokens*: every checked request's generated tokens
were produced by a prefill (state left at the prompt's last real token
of a padded bucket) and decode steps through the state slots and the
paged pool. The reference (``reference/jamba_ssm.py``, float32 at the
highest precision, a plain scan) runs its full forward over prompt +
generated tokens; each generated token's row is held to two limits:
the reference's best logit less its logit at the engine's token
(``LOGIT_TOLERANCE``), and the engine's own best logit — returned
beside the token, ``Future.token_records`` — against the reference's
logit at that token (``VALUE_TOLERANCE``). The 8-bit control
(``all_8bit``: every matrix of the reference rounded to 8 bits) has to
fail them.

*The mixer as this backend runs it* (``hetu_tpu/models/ssm_hybrid.py``
over ``ops/ssm.py``: the scan and step kernels on a TPU), float32, on
the reference's own input to the first Mamba layer for the first
checked request: a prefill over the prompt padded to its bucket, then
one step a generated token through a state slot, against the
reference's mixer on the same rows; every row's relative error within
``MIXER_TOLERANCE``. Here the whole-model noise of bfloat16 matrices is
absent, so this part sees what the first cannot: a state kept in
bfloat16 (the control ``state_bf16``, which has to fail it).

Each of the reference's ``MUTANTS`` is run through BOTH parts on the
first request and logged with its readings; one that passes both fails
the run.
"""
import collections.abc

import numpy as np

from benchmark.harness.session import executor_seed
from benchmark.reference import jamba_ssm as reference
# what the parent lacks: it fails the cell here, in seconds
from hetu_tpu.models import ssm_hybrid

# Each limit lies between the sound engine's largest reading and the
# control's smallest (PERF.md section 4 has the readings).
LOGIT_TOLERANCE = 0.4
VALUE_TOLERANCE = 0.4
MIXER_TOLERANCE = 1e-4


def model_config(config, dtype=None):
    return ssm_hybrid.SSMHybridConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        ssm_state_size=config["mamba_d_state"],
        ssm_conv_width=config["mamba_d_conv"],
        ssm_dt_rank=config["mamba_dt_rank"],
        ssm_expand=config["mamba_expand"],
        head_dim=config["assumed"]["head_dim"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=dtype or config["serve_dtype"])


def train_flops_per_token(config, seq_len):
    raise NotImplementedError(
        "the jamba_ssm family is serving only: the program has no "
        "training graph for a selective scan")


def seeded_weights(config, seed):
    """Every serving parameter, made on the device from the seed, one
    jitted call a (shape, kind), as the file's ``assumed.weights``
    says."""
    import jax
    import jax.numpy as jnp

    a = config["assumed"]
    dtype = jnp.dtype(config["serve_dtype"])
    key = jax.random.key(executor_seed(seed), impl="rbg")
    makers = {}

    def draw(shape, kind):
        if kind == "matrix":
            return lambda k: (a["initializer_std"] * jax.random.normal(
                k, shape, jnp.float32)).astype(dtype)
        if kind in ("norm", "skip"):
            return lambda k: jnp.ones(shape, jnp.float32)
        if kind in ("conv", "bias"):
            bound = config["mamba_d_conv"] ** -0.5
            return lambda k: jax.random.uniform(
                k, shape, jnp.float32, -bound, bound)
        if kind == "a_log":
            return lambda k: jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32)), shape)
        if kind == "dt_bias":
            lo, hi = np.log(a["dt_min"]), np.log(a["dt_max"])

            def dt_bias(k):     # softplus(dt_bias) is log-uniform
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                lo, hi))
                return dt + jnp.log(-jnp.expm1(-dt))
            return dt_bias
        raise ValueError(f"no initialiser for a {kind!r} parameter")

    def make(shape, kind):
        if (shape, kind) not in makers:
            makers[shape, kind] = jax.jit(draw(shape, kind))
        return makers[shape, kind]

    shapes = ssm_hybrid.ssm_hybrid_param_shapes(model_config(config))
    return {name: make(shape, kind)(jax.random.fold_in(key, i))
            for i, (name, (shape, kind)) in enumerate(sorted(
                shapes.items()))}


class HeldOnce(collections.abc.Mapping):
    """The parameters by their checkpoint names, for the reference,
    without holding the model twice: a Mamba layer's parameters live in
    the ENGINE's stacks (``params["runs"]``: axis 0 is a run's layers),
    and one asked for by name is cut out of its stack; the rest
    (``own``) are the arrays the engine holds too."""

    def __init__(self, own, stacked):
        self._own, self._stacked = own, stacked

    def __getitem__(self, name):
        if name in self._own:
            return self._own[name]
        stack, layer = self._stacked[name]
        return stack[layer]

    def __iter__(self):
        yield from self._own
        yield from self._stacked

    def __len__(self):
        return len(self._own) + len(self._stacked)


def build_engine(config, engine_kw, seed):
    from hetu_tpu.serving.scheduler import ContinuousBatchingEngine
    cfg = model_config(config)
    weights = seeded_weights(config, seed)

    def hand_over(name):
        """A Mamba layer's parameter leaves ``weights`` for the engine,
        which stacks it (``a_log`` stays: the engine holds ``-exp`` of
        it, transposed)."""
        layer = name.split("_")[1][1:]
        stacked = layer.isdigit() and not cfg.is_attention(int(layer)) \
            and not name.endswith("a_log")
        return weights.pop(name) if stacked else weights[name]

    engine = ContinuousBatchingEngine(cfg, hand_over, **engine_kw)
    stacked, mamba = {}, [i for i in range(cfg.num_hidden_layers)
                          if not cfg.is_attention(i)]
    for run, _ in engine.params["runs"]:
        for short, stack in (run or {}).items():
            if short != "a_t":
                for j in range(stack.shape[0]):
                    stacked[f"lm_h{mamba[j]}_{short}"] = (stack, j)
        mamba = mamba[len(run["mixer_norm"]) if run else 0:]
    return engine, HeldOnce(weights, stacked)


def engine_reference_logits(config, weights, tokens, positions,
                            pad_to=None):
    return reference.logits_at(weights, config, tokens, positions, pad_to)


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def _bucket(p):
    """The engine's prompt bucket of a ``p``-token prompt (the power of
    two at or above it); for a prompt that fills its bucket, the next:
    the fault has to have padding to run over."""
    bucket = 1 << (p - 1).bit_length()
    return bucket if bucket > p else 2 * p


def logit_readings(config, weights, prompt, out, record, mutant=None):
    """The first part's readings of one request, a generated token a
    row; with ``mutant`` the reference runs that fault."""
    p, new = len(prompt), len(out)
    rows = np.arange(p - 1, p - 1 + new)
    tokens = np.concatenate([prompt, out[:-1]])
    logits = reference.forward(weights, config, tokens, rows, mutant,
                               prompt_len=p, bucket=_bucket(p))
    chosen = logits[np.arange(new), out]
    return {"gap": logits.max(axis=-1) - chosen,
            "value": np.abs(record["best_logit"] - chosen)}


def within_limits(readings):
    # ``not (a <= b)``: a reading that is not a number fails
    return bool((readings["gap"] <= LOGIT_TOLERANCE).all()
                and (readings["value"] <= VALUE_TOLERANCE).all())


def _worst(readings):
    return {"worst_" + k: float(np.max(v)) for k, v in readings.items()}


def program_mixer(config, weights, layer, u, p):
    """The PROGRAM's mixer of layer ``layer`` (float32) over the normed
    rows ``u [T, hidden]`` as the engine walks them: a prefill over the
    first ``p`` padded to their bucket, then a step a row through slot 1
    of a two-slot, one-layer pool. Returns ``[T, hidden]`` float32 numpy."""
    import jax
    import jax.numpy as jnp
    cfg = model_config(config, "float32")
    blk = ssm_hybrid.layer_params(cfg, weights.__getitem__, layer)
    bucket = 1 << (p - 1).bit_length()
    d, n, k = cfg.d_inner, cfg.ssm_state_size, cfg.ssm_conv_width
    u = jnp.asarray(u, jnp.float32)
    padded = jnp.concatenate(
        [u[:p], jnp.broadcast_to(u[p - 1], (bucket - p, u.shape[1]))])

    def prefill(blk, rows):
        with jax.default_matmul_precision("highest"):
            return ssm_hybrid.mixer_prefill(
                cfg, blk, rows[None], jnp.zeros((1, k - 1, d)),
                jnp.zeros((1, n, d)), jnp.asarray([p], jnp.int32))

    def step(blk, row, tail, pool):
        with jax.default_matmul_precision("highest"):
            return ssm_hybrid.mixer_step(
                cfg, blk, row[None], tail, pool,
                jnp.asarray([1], jnp.int32), 0)

    out, tail, state = jax.jit(prefill)(blk, padded)
    outs = [np.asarray(out[0, :p])]
    pool = jnp.zeros((2, 1, n, d), jnp.float32).at[1, 0].set(state[0])
    step = jax.jit(step)
    for row in u[p:]:
        out, tail, pool = step(blk, row, tail, pool)
        outs.append(np.asarray(out))
    return np.concatenate(outs)


def mixer_readings(config, weights, layer, u, p, mutant=None,
                   program=None):
    """The second part's reading: the largest relative error of a row
    of the program's mixer output against the reference's (or a fault
    of it) on the same rows."""
    if program is None:
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
        want, state = reference.mamba_layer(
            w, np.asarray(u, np.float32), config["rms_norm_eps"], mutant,
            p, state)
    want = np.asarray(want)[rows]
    error = np.linalg.norm(program - want, axis=1) \
        / np.linalg.norm(want, axis=1)
    return {"mixer_error": error}, program


def check_generated(config, weights, prompts, outs, records, log):
    """``correct`` of this family (the module docstring says what it
    holds the engine to)."""
    model = model_config(config).serving_model()
    records = [model.read_records(r) for r in records]
    ok = True
    for prompt, out, record in zip(prompts, outs, records):
        readings = logit_readings(config, weights, prompt, out, record)
        good = within_limits(readings)
        log(dict(_worst(readings), check="generated_tokens_vs_reference",
                 prompt_len=len(prompt), tokens=out.tolist(),
                 logit_gaps=readings["gap"].tolist(),
                 value_errors=readings["value"].tolist(),
                 limits=[LOGIT_TOLERANCE, VALUE_TOLERANCE], ok=good))
        ok = ok and good

    # the mixer as this backend runs it, on the first request's rows of
    # the first Mamba layer
    prompt, out, record = prompts[0], outs[0], records[0]
    p = len(prompt)
    layer = next(i for i in range(config["num_hidden_layers"])
                 if not reference.is_attention(config, i))
    tokens = np.concatenate([prompt, out[:-1]])
    _, u = reference.forward(weights, config, tokens, [p - 1],
                             want_layer=layer)
    u = np.asarray(u)
    reading, program = mixer_readings(config, weights, layer, u, p)
    good = bool((reading["mixer_error"] <= MIXER_TOLERANCE).all())
    log(dict(_worst(reading), check="program_mixer", layer=layer,
             rows=len(u), prompt_len=p, limit=MIXER_TOLERANCE, ok=good))
    ok = ok and good

    # the controls and the mutants, through the same comparisons on the
    # first request: each has to fail one of them
    caught = {}
    for fault in reference.CONTROLS + reference.MUTANTS:
        by = {}
        if fault != "state_bf16":
            readings = logit_readings(config, weights, prompt, out,
                                      record, fault)
            by["logits"] = not within_limits(readings)
            by.update(_worst(readings))
        if fault != "all_8bit":
            reading, _ = mixer_readings(config, weights, layer, u, p,
                                        fault, program)
            by["mixer"] = not bool(
                (reading["mixer_error"] <= MIXER_TOLERANCE).all())
            by.update(_worst(reading))
        caught[fault] = by.get("logits", False) or by.get("mixer", False)
        log(dict(by, check="control" if fault in reference.CONTROLS
                 else "mutant", fault=fault, caught=caught[fault]))
    return ok and all(caught.values())
