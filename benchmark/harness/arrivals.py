"""The one generator of serving traffic: a traffic file's parameters
and a seed in, a schedule of requests out.

When each request arrives, how long its prompt is and how many tokens
it asks for are a pure function of the TRAFFIC FILE (its
``population_seed``, rate and distributions) and of the schedule's
length; ``--seed`` draws the token ids (and, in the family file, the
weights). Every seed so offers the same work at the same moments.
It was not always so: with the same gaps and sizes merely permuted by
the seed, six seeds spread the 95th percentile by 42% and the completed
tokens/s by 12% of their medians, while one seed repeated four times
stayed within 1.1% and 0.4% (my chip runs, PR 23) — the seed was
changing the work, and the order is part of the work.

Parameters: ``rate_per_s`` (mean arrivals per second; gaps are
exponential, scaled so the last arrival falls at the end of the
schedule), ``prompt_len`` / ``output_len`` (``{"dist": "lognormal",
"median", "sigma", "min", "max"}`` or ``{"dist": "fixed", "value"}``),
``population_seed``.
"""
import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float            # seconds after the schedule starts
    prompt: np.ndarray      # int32 token ids
    max_new: int


def lengths(spec, n, rng):
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        draw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(draw), spec["min"], spec["max"]).astype(
            np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def population(traffic, total_seconds, rate_per_s=None):
    """(gaps [n], prompt lengths [n], output lengths [n]) — a pure
    function of the traffic file, the schedule's length and the rate."""
    rate = traffic["rate_per_s"] if rate_per_s is None else rate_per_s
    n = max(1, int(round(rate * total_seconds)))
    rng = np.random.RandomState(traffic["population_seed"])
    gaps = rng.exponential(1.0, n)
    gaps *= total_seconds / gaps.sum()
    return (gaps, lengths(traffic["prompt_len"], n, rng),
            lengths(traffic["output_len"], n, rng))


def schedule(traffic, seed, total_seconds, vocab_size, rate_per_s=None):
    """The requests of one run, in order of their due times."""
    gaps, prompt_lens, output_lens = population(
        traffic, total_seconds, rate_per_s)
    due = np.cumsum(gaps)
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    tokens = rng.randint(0, vocab_size, int(prompt_lens.sum())).astype(
        np.int32)
    ends = np.cumsum(prompt_lens)
    return [Request(float(due[i]), tokens[ends[i] - prompt_lens[i]:ends[i]],
                    int(output_lens[i])) for i in range(len(gaps))]
