"""The one traffic generator: reads a mix's data file and makes its inputs
from ``--seed``.

A mix is ``bench/traffic/<name>.json``.  Its ``kind`` picks what is made:

* ``traces``: batches of address traces for the sweep cells, each trace
  from one of the mix's ``families`` in turn.  ``paper`` is the stand-in
  for the paper's unpublished 1000-address program trace, calibrated to
  Table 1's hit ratios; ``zipf``, ``scan_mix`` and ``markov`` are the
  synthetic locality models.  All are copies of ``repro.core.traces``
  with the same parameters; the Markov one is vectorised.
* ``prompts``: batches of token-id prompts for the serving cells, uniform
  over ``[1, vocab)``, one fixed prompt and answer length per mix.

Every batch and every trace has a random stream of its own, keyed by
``(seed, batch, trace)`` through ``numpy.random.SeedSequence``, so any
whole-number seed works and the same seed gives the same inputs.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def load_mix(name: str, *, rehearsal: bool = False,
             root: pathlib.Path = ROOT) -> dict:
    """The mix's parameters; ``rehearsal`` applies its tiny CPU sizes."""
    mix = json.loads((root / "bench" / "traffic" / f"{name}.json")
                     .read_text())
    if rehearsal:
        mix.update(mix.get("rehearsal", {}))
    mix.pop("rehearsal", None)
    mix["name"] = name
    return mix


def stream(seed: int, *key: int) -> np.random.RandomState:
    """A legacy-API random stream keyed by ``(seed, *key)`` (any ints)."""
    return np.random.RandomState(np.random.MT19937(
        np.random.SeedSequence([int(seed) % 2**63, *map(int, key)])))


# ---------------------------------------------------------------------------
# address-trace families (copies of repro.core.traces, same parameters)
# ---------------------------------------------------------------------------


def _zipf_probs(n: int, alpha: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    return p / p.sum()


def trace_zipf(rng, n_accesses: int, n_blocks: int = 1_000,
               alpha: float = 0.8) -> np.ndarray:
    """Zipf(alpha) accesses over ``n_blocks`` blocks."""
    return rng.choice(n_blocks, size=n_accesses,
                      p=_zipf_probs(n_blocks, alpha)).astype(np.int64)


def trace_scan_mix(rng, n_accesses: int, hot_blocks: int = 100,
                   scan_blocks: int = 500, scan_every: int = 1_000,
                   scan_len: int = 250, alpha: float = 1.0) -> np.ndarray:
    """A zipf-hot working set polluted by periodic one-time scans."""
    p = _zipf_probs(hot_blocks, alpha)
    out: list = []
    scan_pos = hot_blocks
    while len(out) < n_accesses:
        out.extend(rng.choice(hot_blocks,
                              size=min(scan_every, n_accesses - len(out)),
                              p=p))
        remaining = n_accesses - len(out)
        if remaining <= 0:
            break
        for i in range(min(scan_len, remaining)):
            out.append(hot_blocks + (scan_pos - hot_blocks + i) % scan_blocks)
        scan_pos += scan_len
    return np.asarray(out[:n_accesses], dtype=np.int64)


def trace_markov(rng, n_accesses: int, n_regions: int = 8,
                 region_size: int = 64, p_stay: float = 0.95) -> np.ndarray:
    """Working-set model: uniform accesses inside one region, and a jump to
    a uniformly drawn region with probability ``1 - p_stay`` before each
    access (the loop of ``repro.core.traces.trace_markov``, vectorised)."""
    jump = rng.rand(n_accesses) > p_stay
    target = rng.randint(n_regions, size=n_accesses)
    # the region in force at t is the target of the last jump at or before
    # t, or region 0 before the first jump
    last = np.maximum.accumulate(np.where(jump, np.arange(n_accesses), -1))
    region = np.where(last >= 0, target[np.maximum(last, 0)], 0)
    return (region * region_size
            + rng.randint(region_size, size=n_accesses)).astype(np.int64)


def trace_paper(rng, n_accesses: int, hot: int = 130, alpha: float = 0.8,
                scan_frac: float = 0.12, burst: int = 15) -> np.ndarray:
    """The Table-1 stand-in: a zipf-skewed hot set of ``hot`` blocks with
    bursts of ``burst`` one-time addresses spread evenly through it, a
    ``scan_frac`` share of the trace (``repro.core.traces.paper_trace``,
    calibrated at 1000 accesses to span Table 1's hit-ratio band)."""
    hot_stream = rng.choice(hot, size=n_accesses - int(n_accesses * scan_frac),
                            p=_zipf_probs(hot, alpha))
    n_bursts = max(1, int(n_accesses * scan_frac) // burst)
    gap = len(hot_stream) // (n_bursts + 1)
    out, hi, sp = [], 0, 0
    for _ in range(n_bursts):
        out.extend(hot_stream[hi:hi + gap])
        hi += gap
        out.extend(hot + sp + i for i in range(burst))
        sp += burst
    out.extend(hot_stream[hi:])
    return np.asarray(out[:n_accesses], dtype=np.int64)


FAMILIES = {"paper": trace_paper, "zipf": trace_zipf,
            "scan_mix": trace_scan_mix, "markov": trace_markov}


def family_of(mix: dict, trace: int) -> dict:
    """The family entry that trace index ``trace`` of a batch uses."""
    fams = mix["families"]
    return fams[trace % len(fams)]


def trace_batch(mix: dict, seed: int, batch: int) -> np.ndarray:
    """``(n_traces, length)`` int32 traces of one batch."""
    rows = []
    for i in range(mix["n_traces"]):
        fam = dict(family_of(mix, i))
        gen = FAMILIES[fam.pop("family")]
        rows.append(gen(stream(seed, batch, i), mix["length"], **fam))
    return np.stack(rows).astype(np.int32)


# ---------------------------------------------------------------------------
# prompts
# ---------------------------------------------------------------------------


def prompt_batch(mix: dict, seed: int, batch: int, vocab: int) -> np.ndarray:
    """``(batch_size, prompt_len)`` int32 token ids in ``[1, vocab)``."""
    return stream(seed, batch).randint(
        1, vocab, size=(mix["batch"], mix["prompt_len"])).astype(np.int32)


def make_batches(mix: dict, seed: int, vocab: int = 0) -> list:
    """Every input batch of a run: ``mix["batches"]`` distinct batches plus
    one more for the warm-up, which the window never repeats."""
    n = mix["batches"] + 1
    if mix["kind"] == "traces":
        return [trace_batch(mix, seed, b) for b in range(n)]
    if mix["kind"] == "prompts":
        return [prompt_batch(mix, seed, b, vocab) for b in range(n)]
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")
