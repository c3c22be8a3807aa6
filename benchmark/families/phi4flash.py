"""``phi4flash`` family (a decoder-hybrid-decoder: Mamba-1 and window
differential attention below ONE full-attention layer, gated memory
units and cross-attention onto that layer's cache above it; LayerNorms,
no positions, tied head): from a configuration file to what the serving
driver runs. Serving only: the program has no graph op with a gradient
for a scan.

Offers the drivers ``build_engine``, ``engine_reference_logits`` and
``check_generated``. **What ``correct`` holds the engine to**:

*The timed path's tokens*: every checked request's generated tokens
were produced by a prefill (the cross-decoder on the prompt's last row
alone, the state left at the prompt's last real token of a padded
bucket, the last window of rows in each ring) and decode steps through
the state slots, the rings and the one shared pool. The reference
(``reference/phi4flash.py``: float32 at the highest precision, every
layer at every position) runs its full forward over prompt + generated
tokens; each generated token's row is held to two limits: the
reference's best logit less its logit at the engine's token
(``LOGIT_TOLERANCE``), and the engine's own best logit — returned
beside the token, ``Future.token_records`` — against the reference's
logit at that token (``VALUE_TOLERANCE``). The 8-bit control
(``all_8bit``) has to fail them.

*Four mixers as this backend runs them*, on the reference's own inputs
to them for one checked request (the shortest of at least four
windows, so that both the band's edge and the cross layers' reach lie
inside it):

* the Mamba mixer of layer ``half`` (``models/ssm_hybrid.py`` over
  ``ops/ssm.py``: the scan and step kernels on a TPU), float32: a
  prefill over the prompt padded to its bucket, then a step a generated
  token through a state slot; every row of its output and of ``m``,
  the scan's output it hands on, within ``MIXER_TOLERANCE`` of the
  reference's. It sees what the logits cannot: a state kept in bfloat16
  (``state_bf16``), what a padded bucket or a reused slot leaves;
* the gated memory unit of layer ``half + 2``
  (``shared_cache_decoder.gated_memory``), float32, on the PROGRAM's
  ``m`` from the part above: within ``MIXER_TOLERANCE``; it sees a
  memory taken behind the gate or from another layer;
* the window differential layer 1 in the serving dtype: the banded
  two-map flash call over the padded prompt, then a step a generated
  token against a ring
  (``ops/attention.py:diff_prefill_attention``, ``diff_rows_attention``)
  within ``ATTENTION_TOLERANCE`` (relative, a row); it sees the pairing,
  ``lambda``, the pair norm, the band, and this layer's matrices in 8
  bits (``all_8bit``);
* the first cross layer (``half + 3``) in the serving dtype, a
  generated token a row against the reference's own rows of layer
  ``half + 1`` (``shared_cache_decoder.diff_attention_rows``), within
  ``ATTENTION_TOLERANCE``; it sees how far back a cross layer reads.

Each of the reference's ``MUTANTS`` and ``CONTROLS`` is run through the
part that can see it and, where that lets it through (and for the 8-bit
control in any case), through the logits, and logged with its readings;
one that passes all fails the run.
"""
import numpy as np

from benchmark.families.jamba_ssm import HeldOnce, _bucket
from benchmark.harness.session import executor_seed
from benchmark.reference import phi4flash as reference
# what the parent lacks: it fails the cell here, in seconds
from hetu_tpu.models import shared_cache_decoder as model

# Each limit lies between the sound engine's largest reading and the
# control's smallest (PERF.md section 4 has the readings).
LOGIT_TOLERANCE = 0.4
VALUE_TOLERANCE = 0.4
MIXER_TOLERANCE = 1e-4
ATTENTION_TOLERANCE = 0.02

# which part besides the logits can see a fault
PARTS = {"all_8bit": "attention", "state_bf16": "mixer", "state_at_bucket_end": "mixer",
         "slot_not_zeroed": "mixer", "memory_after_gate": "gate",
         "memory_of_layer_14": "gate", "second_map_dropped": "attention",
         "lambda_init_constant": "attention",
         "pair_norm_dropped": "attention", "pairs_by_halves": "attention",
         "window_halved": "attention", "cross_reads_window": "cross"}


def model_config(config, dtype=None):
    a = config["assumed"]
    return model.SharedCacheConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        sliding_window=config["sliding_window"],
        mb_per_layer=config["mb_per_layer"],
        head_dim=a["head_dim"],
        ssm_state_size=config["mamba_d_state"],
        ssm_conv_width=config["mamba_d_conv"],
        ssm_dt_rank=config["mamba_dt_rank"],
        ssm_expand=config["mamba_expand"],
        layer_norm_eps=config["layer_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        dtype=dtype or config["serve_dtype"])


def train_flops_per_token(config, seq_len):
    raise NotImplementedError(
        "the phi4flash family is serving only: the program has no "
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

    def normal(shape, std, out):
        return lambda k: (std * jax.random.normal(
            k, shape, jnp.float32)).astype(out)

    def draw(shape, kind):
        if kind == "matrix":
            return normal(shape, a["initializer_std"], dtype)
        if kind == "proj_bias":
            return normal(shape, a["initializer_std"], jnp.float32)
        if kind == "lambda":
            return normal(shape, a["lambda_std"], jnp.float32)
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

    shapes = model.shared_cache_param_shapes(model_config(config))
    return {name: make(shape, kind)(jax.random.fold_in(key, i))
            for i, (name, (shape, kind)) in enumerate(sorted(
                shapes.items()))}


def build_engine(config, engine_kw, seed):
    from hetu_tpu.serving.scheduler import ContinuousBatchingEngine
    cfg = model_config(config)
    weights = seeded_weights(config, seed)
    shapes = model.shared_cache_param_shapes(cfg)
    alone = (cfg.half, cfg.half + 1)    # the two layers no run holds

    def hand_over(name):
        """A matrix of a layer the engine stacks leaves ``weights`` for
        it; the rest (and what the engine transforms: ``a_log``, the
        ``lambda`` vectors) stays for the reference too."""
        layer = name.split("_")[1][1:]
        stacked = (shapes[name][1] == "matrix" and layer.isdigit()
                   and int(layer) not in alone)
        return weights.pop(name) if stacked else weights[name]

    engine = ContinuousBatchingEngine(cfg, hand_over, **engine_kw)
    stacked = {}
    for run, start in (("lower", 0), ("upper", cfg.half + 2)):
        for which, offset in (("first", 0), ("second", 1)):
            for short, stack in engine.params[run][which].items():
                for j in range(stack.shape[0]):
                    name = f"lm_h{start + offset + 2 * j}_{short}"
                    if name in shapes and name not in weights:
                        stacked[name] = (stack, j)
    held = HeldOnce(weights, stacked)
    # ``check_generated`` closes the engine and lets its pools go before
    # the reference runs (the driver has the generated tokens by then)
    held.engine = engine
    return engine, held


def engine_reference_logits(config, weights, tokens, positions,
                            pad_to=None):
    return reference.logits_at(weights, config, tokens, positions, pad_to)


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def logit_readings(config, weights, prompt, out, record, mutant=None,
                   want_layers=()):
    """The first part's readings of one request, a generated token a
    row; with ``mutant`` the reference runs that fault. With
    ``want_layers`` also what ``reference.forward`` returns of them."""
    p, new = len(prompt), len(out)
    rows = np.arange(p - 1, p - 1 + new)
    tokens = np.concatenate([prompt, out[:-1]])
    logits = reference.forward(weights, config, tokens, rows, mutant,
                               prompt_len=p, bucket=_bucket(p),
                               want_layers=want_layers)
    if want_layers:
        logits, wanted = logits
    chosen = logits[np.arange(new), out]
    readings = {"gap": logits.max(axis=-1) - chosen,
                "value": np.abs(record["best_logit"] - chosen)}
    return (readings, wanted) if want_layers else readings


def within_limits(readings):
    # ``not (a <= b)``: a reading that is not a number fails
    return bool((readings["gap"] <= LOGIT_TOLERANCE).all()
                and (readings["value"] <= VALUE_TOLERANCE).all())


def _worst(readings):
    return {"worst_" + k: float(np.max(v)) for k, v in readings.items()}


def _relative(got, want):
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)


def program_mixer(config, weights, layer, u, p):
    """The PROGRAM's Mamba mixer of ``layer`` (float32) over the normed
    rows ``u [T, hidden]`` as the engine walks them: a prefill over the
    first ``p`` padded to their bucket, then a step a row through slot 1
    of a two-slot, one-layer pool. Returns ``(out [T, hidden], m [T,
    d])`` float32 numpy, ``m`` the scan's output before the gate."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models import ssm_hybrid
    cfg = model_config(config, "float32")
    blk = model.layer_params(cfg, weights.__getitem__, layer)
    bucket = 1 << (p - 1).bit_length()
    d, n, k = cfg.d_inner, cfg.ssm_state_size, cfg.ssm_conv_width
    u = jnp.asarray(u, jnp.float32)
    padded = jnp.concatenate(
        [u[:p], jnp.broadcast_to(u[p - 1], (bucket - p, u.shape[1]))])

    def prefill(blk, rows):
        with jax.default_matmul_precision("highest"):
            y, z, tail, s = ssm_hybrid.scan_prefill(
                cfg, blk, rows[None], jnp.zeros((1, k - 1, d)),
                jnp.zeros((1, n, d)), jnp.asarray([p], jnp.int32))
            return ssm_hybrid._gated_out(blk, y, z), y, tail, s

    def step(blk, row, tail, pool):
        with jax.default_matmul_precision("highest"):
            y, z, window, pool = ssm_hybrid.scan_step(
                cfg, blk, row[None], tail, pool,
                jnp.asarray([1], jnp.int32), 0)
            return ssm_hybrid._gated_out(blk, y, z), y, window[:, 1:], pool

    out, y, tail, state = jax.jit(prefill)(blk, padded)
    outs, ys = [np.asarray(out[0, :p])], [np.asarray(y[0, :p])]
    pool = jnp.zeros((2, 1, n, d), jnp.float32).at[1, 0].set(state[0])
    step = jax.jit(step)
    for row in u[p:]:
        out, y, tail, pool = step(blk, row, tail, pool)
        outs.append(np.asarray(out))
        ys.append(np.asarray(y))
    return np.concatenate(outs), np.concatenate(ys)


def mixer_readings(config, weights, layer, u, p, program, mutant=None):
    """The Mamba part's reading: the largest relative error of a row of
    the program's mixer output, and of its ``m``, against the
    reference's (or a fault of it) on the same rows."""
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
        want, memory, state = reference.mamba_layer(
            w, np.asarray(u, np.float32), mutant, state)
    return {"mixer_error": _relative(program[0], np.asarray(want)[rows]),
            "memory_error": _relative(program[1],
                                      np.asarray(memory)[rows])}


def program_gate(config, weights, layer, u, m):
    """The PROGRAM's gated memory unit of ``layer`` (float32) on the
    normed rows ``u [T, hidden]`` and the memory ``m [T, d]``."""
    import jax
    import jax.numpy as jnp
    cfg = model_config(config, "float32")
    blk = model.layer_params(cfg, weights.__getitem__, layer)

    def gate(blk, u, m):
        with jax.default_matmul_precision("highest"):
            return model.gated_memory(blk, u, m)

    return np.asarray(jax.jit(gate)(blk, jnp.asarray(u, jnp.float32),
                                    jnp.asarray(m, jnp.float32)))


def jnp_f32(a):
    import jax.numpy as jnp
    return jnp.asarray(a, jnp.float32)


def gate_readings(weights, layer, u, memory, program):
    """The gate part's reading against the reference's gate on ITS
    memory (a fault's, where ``memory`` is one's)."""
    want = reference.gate_layer(reference.layer_weights(weights, layer),
                                jnp_f32(u), jnp_f32(memory))
    return {"gate_error": _relative(program, np.asarray(want))}


def program_attention(config, weights, layer, u, p, block_size=16):
    """The PROGRAM's window differential layer ``layer`` in the serving
    dtype on the normed rows ``u [T, hidden]`` as the engine walks them:
    the banded two-map call over the first ``p`` padded to their bucket,
    then a step a row against a ring of ``ceil(window / block) + 1``
    blocks that holds the prompt's last window. Returns ``[T, hidden]``
    float32 numpy."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import attention as ops
    cfg = model_config(config)
    dtype = jnp.dtype(cfg.dtype)
    blk = model.layer_params(cfg, weights.__getitem__, layer)
    t, window = len(u), cfg.sliding_window
    ring = (-(-window // block_size) + 1) * block_size
    bucket = 1 << (p - 1).bit_length()
    u = jnp.asarray(u, dtype)
    padded = jnp.concatenate(
        [u[:p], jnp.broadcast_to(u[p - 1], (bucket - p, u.shape[1]))])
    heads = (1, bucket, cfg.num_key_value_heads, cfg.head_dim)

    def prefill(blk, rows):
        q, k, v = model._window_qkv(cfg, blk, rows[None])
        maps = ops.diff_prefill_attention(
            q, k.reshape(heads), v.reshape(heads), model._scale(cfg),
            window=window)
        return model._attention_out(cfg, blk, maps, dtype), k, v

    def step(blk, row, ring_k, ring_v, position):
        q, k, v = model._window_qkv(cfg, blk, row[None])
        at = position % ring
        ring_k, ring_v = ring_k.at[0, at].set(k[0]), ring_v.at[0, at].set(
            v[0])
        maps = ops.diff_rows_attention(
            q, ring_k, ring_v, ops.ring_valid(ring, position[None], window),
            model._scale(cfg))
        return model._attention_out(cfg, blk, maps, dtype), ring_k, ring_v

    out, k, v = jax.jit(prefill)(blk, padded)
    outs = [np.asarray(out[0, :p], np.float32)]
    kept = np.arange(max(0, p - window), p)
    ring_k, ring_v = (jnp.zeros((1, ring, x.shape[-1]), dtype)
                      .at[0, kept % ring].set(x[0, kept]) for x in (k, v))
    step = jax.jit(step)
    for i in range(p, t):
        out, ring_k, ring_v = step(blk, u[i], ring_k, ring_v,
                                   jnp.asarray(i, jnp.int32))
        outs.append(np.asarray(out, np.float32))
    return np.concatenate(outs)


def attention_readings(config, weights, layer, u, program, mutant=None):
    """The attention part's reading: the relative error of each row of
    the program's window layer against the reference's (or a fault of
    it) on the same normed rows."""
    want, _ = reference.attention_layer(
        reference.layer_weights(weights, layer, mutant), jnp_f32(u), config,
        layer, np.ones(len(u), bool), None, mutant)
    return {"attention_error": _relative(program, np.asarray(want))}


def program_cross(config, weights, layer, u, kv, p):
    """The PROGRAM's cross layer ``layer`` in the serving dtype on the
    normed rows ``u [T, hidden]`` from the prompt's last on, one a
    batch row as a decode step holds them, against the rows ``kv``
    (``k``, ``v [T, kv_heads x D]``) in position order. Returns ``[T -
    p + 1, hidden]`` float32 numpy."""
    import jax
    import jax.numpy as jnp
    cfg = model_config(config)
    dtype = jnp.dtype(cfg.dtype)
    blk = model.layer_params(cfg, weights.__getitem__, layer)
    t = len(u)
    at = jnp.arange(p - 1, t)

    def rows(blk, a, k, v):
        k, v = (jnp.broadcast_to(x[None], (len(at), *x.shape))
                for x in (k, v))
        return model.diff_attention_rows(cfg, blk, a, k, v, at)

    return np.asarray(jax.jit(rows)(
        blk, jnp.asarray(u, dtype)[p - 1:],
        *(jnp.asarray(x, dtype) for x in kv)), np.float32)


def cross_readings(config, weights, layer, u, kv, p, program, mutant=None):
    """The cross part's reading, on the rows from the prompt's last
    on."""
    want, _ = reference.attention_layer(
        reference.layer_weights(weights, layer), jnp_f32(u), config, layer,
        np.ones(len(u), bool), tuple(jnp_f32(x) for x in kv), mutant)
    return {"cross_error": _relative(program, np.asarray(want)[p - 1:])}


def _fault_request(prompts, window):
    """Which checked request the faults and the parts run on: the
    shortest prompt of at least four windows, else the longest."""
    lengths = [len(p) for p in prompts]
    long = [n for n in lengths if n >= 4 * window]
    return lengths.index(min(long) if long else max(lengths))


def _release_pools(weights):
    """The engine that made the tokens is done when they are checked:
    stop it and let its pools go (3.65e9 B at the published sizes), so
    that the float32 reference of a prompt in the LONGEST bucket fits
    on the chip beside the parameters (``reference.forward`` holds
    about 0.47e6 B a token: a 10,158-token prompt beside the resident
    pools reached 16.71e9 of 16.91e9, PR 60)."""
    engine = getattr(weights, "engine", None)
    if engine is not None:
        engine.close()
        engine.cache.pools = None


def check_generated(config, weights, prompts, outs, records, log):
    """``correct`` of this family (the module docstring says what it
    holds the engine to)."""
    served = model_config(config).serving_model()
    records = [served.read_records(r) for r in records]
    _release_pools(weights)
    ok = True
    at = _fault_request(prompts, config["sliding_window"])
    half = config["num_hidden_layers"] // 2
    layers = {"mixer": half, "gate": half + 2, "attention": 1,
              "cross": half + 3}
    for i, (prompt, out, record) in enumerate(zip(prompts, outs, records)):
        readings = logit_readings(
            config, weights, prompt, out, record,
            want_layers=(half - 2, *layers.values()) if i == at else ())
        if i == at:     # the parts' inputs come with this forward
            readings, wanted = readings
        good = within_limits(readings)
        log(dict(_worst(readings), check="generated_tokens_vs_reference",
                 prompt_len=len(prompt), tokens=out.tolist(),
                 logit_gaps=readings["gap"].tolist(),
                 value_errors=readings["value"].tolist(),
                 limits=[LOGIT_TOLERANCE, VALUE_TOLERANCE], ok=good))
        ok = ok and good

    prompt, out, record = prompts[at], outs[at], records[at]
    p = len(prompt)
    inputs = {part: np.asarray(wanted[layer]["input"])
              for part, layer in layers.items()}
    kv = wanted[half + 3]["kv"]
    programs = {"mixer": program_mixer(config, weights, half,
                                       inputs["mixer"], p)}
    programs["gate"] = program_gate(config, weights, half + 2,
                                    inputs["gate"], programs["mixer"][1])
    programs["attention"] = program_attention(
        config, weights, 1, inputs["attention"], p)
    programs["cross"] = program_cross(config, weights, half + 3,
                                      inputs["cross"], kv, p)

    def memory_of(mutant):
        """The memory the reference's gate reads, or a fault's: the
        scan's output of the Mamba layer the fault takes it from, on the
        sound forward's input to that layer."""
        if mutant is None:
            return wanted[half + 2]["memory"]
        at = half - 2 if mutant == "memory_of_layer_14" else half
        return reference.mamba_layer(
            reference.layer_weights(weights, at),
            np.asarray(wanted[at]["input"]), mutant)[1]

    def part_readings(part, mutant=None):
        if part == "mixer":
            return mixer_readings(config, weights, half, inputs[part], p,
                                  programs[part], mutant), MIXER_TOLERANCE
        if part == "gate":
            return gate_readings(weights, half + 2, inputs[part],
                                 memory_of(mutant), programs[part]), \
                MIXER_TOLERANCE
        if part == "cross":
            return cross_readings(config, weights, half + 3, inputs[part],
                                  kv, p, programs[part], mutant), \
                ATTENTION_TOLERANCE
        return attention_readings(config, weights, 1, inputs[part],
                                  programs[part], mutant), \
            ATTENTION_TOLERANCE

    def inside(reading, limit):
        return all(bool((v <= limit).all()) for v in reading.values())

    for part, layer in layers.items():
        reading, limit = part_readings(part)
        good = inside(reading, limit)
        log(dict(_worst(reading), check="program_" + part, layer=layer,
                 rows=len(inputs[part]), prompt_len=p, limit=limit,
                 ok=good))
        ok = ok and good

    # the controls and the mutants, through the same comparisons on the
    # same request: each has to fail one of them. The part that can see
    # a fault reads it first (one layer of the reference); the whole
    # forward of the fault (a minute at the published widths) runs for
    # the 8-bit control, whose limits are the logits', and for a fault
    # that its part has let through
    caught = {}
    for fault in reference.CONTROLS + reference.MUTANTS:
        by, part = {}, PARTS.get(fault)
        if part:
            reading, limit = part_readings(part, fault)
            by[part] = not inside(reading, limit)
            by.update(_worst(reading))
        if fault == "all_8bit" or not by.get(part, False):
            readings = logit_readings(config, weights, prompt, out,
                                      record, fault)
            by["logits"] = not within_limits(readings)
            by.update(_worst(readings))
        caught[fault] = any(v for k, v in by.items()
                            if not k.startswith("worst_"))
        log(dict(by, check="control" if fault in reference.CONTROLS
                 else "mutant", fault=fault, caught=caught[fault]))
    return ok and all(caught.values())
