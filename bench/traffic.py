"""The one traffic generator: a mix is a JSON file of parameters under
``bench/traffic/``; this module turns it, a configuration and a seed
into requests or frames.

Every seed gets the same set of sizes: lengths are the quantiles of the
mix's distributions, so the seed only changes which client gets which
length and what the tokens or pixels are.  Runs with different seeds
then do the same work in another order, and their spread is the
system's, not the draw's.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose (tokens, sampling, ...)."""
    return np.random.default_rng([int(seed), int(stream)])


def quantile_lengths(dist: Dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``: a
    log-normal given by its median and sigma, clipped to [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    norm = statistics.NormalDist()
    mu = math.log(dist["median"])
    out = []
    for i in range(n):
        z = norm.inv_cdf((i + 0.5) / n)
        v = round(math.exp(mu + dist["sigma"] * z))
        out.append(int(min(max(v, dist["min"]), dist["max"])))
    return out


def length_pairs(mix: Dict, clients: int) -> List[Tuple[int, int]]:
    """One round of (prompt, output) lengths, one per client.  Prompt
    and output quantiles are paired by a fixed stride, so long prompts
    do not always come with long outputs."""
    prompts = quantile_lengths(mix["prompt"], clients)
    outputs = quantile_lengths(mix["output"], clients)
    stride = mix.get("pair_stride", 7)
    if math.gcd(stride, clients) != 1:
        stride = 1
    return [(prompts[i], outputs[(i * stride) % clients])
            for i in range(clients)]


def closed_loop_requests(mix: Dict, clients: int, vocab: int, seed: int
                         ) -> List[List[Tuple[Tuple[int, ...], int]]]:
    """Per client, its requests in order: (prompt tokens, output
    tokens).  Every round gives each client one request and holds the
    same lengths; which client gets which length follows a fixed
    script, so every seed serves the same lengths at the same moments
    (clients are alike), and the seed only deals the scripts to the
    clients and draws the tokens."""
    if mix["kind"] != "closed_loop":
        raise ValueError(f"mix kind {mix['kind']!r} is not closed_loop")
    pairs = length_pairs(mix, clients)
    script_rng = np.random.default_rng(0)       # the same for every seed
    scripts: List[List[Tuple[int, int]]] = [[] for _ in range(clients)]
    for r in range(mix["rounds"]):
        for k, j in enumerate(script_rng.permutation(clients)):
            plen, out = pairs[j]
            if r == 0 and mix.get("first_round") == "residual":
                # a closed loop in steady state finds each client part
                # way through its request: the first round asks for the
                # rest only, at fixed fractions (i + 0.5) / clients
                frac = ((j * 5) % clients + 0.5) / clients
                out = max(1, math.ceil(out * frac))
            scripts[k].append((plen, out))
    deal, tok_rng = rng_for(seed, 1).permutation(clients), rng_for(seed, 2)
    return [[(tuple(int(t) for t in tok_rng.integers(0, vocab, plen)), out)
             for plen, out in scripts[deal[c]]] for c in range(clients)]


def frame_pool(mix: Dict, cfg: Dict, seed: int) -> np.ndarray:
    """``mix["pool"]`` frames of unsigned ``act_bits``-wide pixels,
    [pool, 1, H, W, C] int32; the stream cycles through them."""
    if mix["kind"] != "frame_stream":
        raise ValueError(f"mix kind {mix['kind']!r} is not frame_stream")
    rng = rng_for(seed, 3)
    shape = (mix["pool"], 1, cfg["frame"], cfg["frame"], cfg["in_channels"])
    return rng.integers(0, 1 << cfg["act_bits"], shape).astype(np.int32)


def sample_indices(n: int, k: int, seed: int) -> List[int]:
    """``k`` of ``n`` indices drawn from the seed (all when k >= n)."""
    if k >= n:
        return list(range(n))
    return sorted(int(i) for i in
                  rng_for(seed, 4).choice(n, size=k, replace=False))
