"""The programs of the models that did NOT change lower to the text
they lowered to before: tiny float32 latent-attention (one residual
stream; a query LoRA with four streams) and state-space hybrid engines,
plain and chunked, every program ``warm_up`` reaches, by the
fingerprint of its StableHLO text (``Lowered.as_text()`` carries no
locations). The fingerprints in ``tests/data/serving_program_text.json``
were taken on the tree BEFORE PR 44 stacked GPT's parameters and pools
over layers (the engine and the cache are shared by all four models),
so this runs without that tree; the two expert models' were taken
again on PR 62's tree, whose router reads a pick's score and whose
expert layer counts its pairs by compare-and-reduce
(``ops/moe.py:_picked``, ``_sorted_pairs``; the state-space hybrid's,
which routes nothing, stayed to the byte). A PR that means to change one
of these programs takes them again::

    python tests/test_serving_program_text.py --write

The text is this jax's spelling of the programs: after a jax (or
StableHLO) upgrade every fingerprint may differ with no program changed.
Take them again then, on a tree whose serving models are known to be
what they were (run the logits tests of ``test_latent_moe_serving.py``
and ``test_ssm_hybrid_serving.py`` first).

Nothing is compiled or run: ``_dispatch`` is stood in for by a recorder
that lowers the call and hands back zeros of the shapes it would return.
"""
import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(HERE)):     # run as a script too
    if path not in sys.path:
        sys.path.insert(0, path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import test_latent_moe_serving as latent  # noqa: E402
import test_ssm_hybrid_serving as hybrid  # noqa: E402

STORE = os.path.join(HERE, "data", "serving_program_text.json")

# model -> (a configuration's content, weights of a seed, an engine)
MODELS = {
    "sarvam": (latent.tiny, latent.family.seeded_weights,
               latent.engine_for),
    "xing": (latent.tiny_xing, latent.xing_family.seeded_weights,
             latent.engine_for),
    "jamba": (hybrid.tiny, hybrid.family.seeded_weights,
              hybrid.engine_for),
}
MODES = {"plain": {}, "chunked": {"prefill_chunk": 8}}


def fingerprints(model, mode):
    """{signature key: sha256 of the lowered text} of every program the
    engine's ``warm_up`` dispatches for prompts of 3..12 tokens and 4
    new ones."""
    make, seeded, engine_for = MODELS[model]
    config = make()
    engine = engine_for(config, seeded(config, 7), max_batch_size=2,
                        max_len=32, **MODES[mode])
    found = {}

    def record(key, fn, *args):
        lowered = fn.lower(*args)
        found[" ".join(map(str, key))] = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), lowered.out_info)

    engine._dispatch = record
    engine.warm_up((3, 12), 4)
    engine.close()
    return found


def _stored():
    with open(STORE) as f:
        return json.load(f)


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("model", list(MODELS))
def test_unchanged_models_lower_to_the_stored_text(model, mode):
    want = _stored()[f"{model}.{mode}"]
    got = fingerprints(model, mode)
    assert sorted(got) == sorted(want)
    assert len(got) >= 6
    changed = [key for key in want if got[key] != want[key]]
    assert not changed, f"{model} ({mode}) lowers to another text: {changed}"


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        sys.exit(__doc__)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    out = {f"{model}.{mode}": fingerprints(model, mode)
           for model in MODELS for mode in MODES}
    with open(STORE, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print({k: len(v) for k, v in out.items()})
