"""One general generator of inputs from a traffic file and a seed.

The seed fills the traffic; it never sizes it. Every seed gives a training
cell the same shapes and a serving cell the same requests (prompt lengths,
answer lengths, arrival times) with other token ids.
"""

import math
import statistics

import numpy as np


def rng_for(seed, *stream):
    """``--seed`` may pass 2**31: SeedSequence takes any non-negative int."""
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


# -- training ----------------------------------------------------------------

def token_batch(seed, step, batch, seq_len, vocab):
    """(tokens, labels) int32 of shape (batch, seq_len): uniform ids, rows all
    different; labels are the next token."""
    toks = rng_for(seed, 1, step).integers(
        0, vocab, size=(batch, seq_len + 1), dtype=np.int64).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def image_batches(seed, traffic, image, classes):
    """``distinct_batches`` batches of uint8 images and int32 labels. Each
    class has a coarse pattern of its own, so that the batch gradient carries
    a signal and is not what is left after the rows cancel. Drawn in float32
    and a batch at a time: the host makes them in every run's set-up."""
    t = traffic
    rng = rng_for(seed, 2)
    coarse = t.get("pattern_cells", 7)
    patterns = t["pattern_amplitude"] * rng.standard_normal(
        (classes, coarse, coarse, 3), dtype=np.float32)
    rep = -(-image // coarse)
    shape = (t["batch"], image, image, 3)
    out = []
    for _ in range(t["distinct_batches"]):
        y = rng.integers(0, classes, size=(t["batch"],)).astype(np.int32)
        x = rng.standard_normal(shape, dtype=np.float32)
        x *= np.float32(t["noise_amplitude"])
        x += np.float32(127.5)
        x += np.repeat(np.repeat(patterns[y], rep, axis=1), rep, axis=2)[
            :, :image, :image]
        np.rint(x, out=x)
        np.clip(x, 0, 255, out=x)
        out.append((x.astype(np.uint8), y))
    return out


# -- serving -----------------------------------------------------------------

def _norm_ppf(p):
    return statistics.NormalDist().inv_cdf(p)


def lognormal_midpoints(n, median, sigma, lo, hi):
    """The n quantile midpoints of a log-normal, rounded and clipped."""
    out = []
    for i in range(n):
        v = median * math.exp(sigma * _norm_ppf((i + 0.5) / n))
        out.append(int(min(hi, max(lo, round(v)))))
    return out


def exponential_midpoints(n, rate):
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def request_count(traffic, seconds):
    return max(1, int(math.floor(traffic["rate_per_s"] * seconds)))


def requests(seed, traffic, seconds, vocab):
    """-> list of dicts (due, prompt, max_new) sorted by due time. Every seed
    offers the same requests at the same moments: the gaps, the prompt
    lengths and the answer lengths each stand in one order, drawn once from
    the traffic file's ``arrival_order_seed`` (bursts where that order puts
    short gaps together); the seed draws the token ids (and, in the runner,
    the weights). Near its knee a server's tails follow which long requests
    meet in the batch: lengths permuted by the seed moved ``itl_p95_ms`` by
    9% from seed to seed where two runs of one seed lay 1 to 3% apart
    (PERF.md section 6, PR 28)."""
    t = traffic
    n = request_count(t, seconds)
    p, a = t["prompt_tokens"], t["answer_tokens"]
    prompts = lognormal_midpoints(n, p["median"], p["sigma"], p["min"], p["max"])
    answers = lognormal_midpoints(n, a["median"], a["sigma"], a["min"], a["max"])
    gaps = exponential_midpoints(n, t["rate_per_s"])
    gaps, prompts, answers = (
        [values[i] for i in rng_for(t["arrival_order_seed"], stream).permutation(n)]
        for values, stream in ((gaps, 5), (prompts, 6), (answers, 7)))
    rng = rng_for(seed, 3)
    limit = t["max_total_tokens"]
    due, out = 0.0, []
    for i in range(n):
        due += gaps[i]
        if prompts[i] + answers[i] > limit:
            raise ValueError("traffic file: prompt + answer over the limit")
        ids = rng.integers(0, vocab, size=(prompts[i],)).astype(np.int32)
        out.append({"due": due, "prompt": ids, "max_new": answers[i]})
    if due > seconds:
        # the midpoints' mean lies under 1/rate, so this does not happen;
        # a traffic file that breaks it is at fault, not the seed
        raise ValueError(f"last arrival {due:.3f} s after the window")
    return out
