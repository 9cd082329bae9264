"""One general traffic generator, driven by the data files in ``traffic/``.

A mix file (``traffic/<name>.json``) holds parameters only; this module
turns them and ``--seed`` into requests.  Every mix is a sequence of
*bursts*: all requests of a burst are due when its
``Engine.serve_requests`` call starts, and bursts follow each other back
to back (BurstGPT, arXiv:2401.17644, describes such arrivals).

Sizes are stratified, not drawn: the ``n`` prompt (or output) lengths of
a burst are the distribution's quantiles at ``(i + 0.5) / n``, clipped,
paired and ordered by one fixed shuffle.  Every seed and every burst
therefore asks for the same work in the same order, like a replayed
trace: with the order drawn from the seed, the schedule, and so the
throughput and the tails, moved by 7% from seed to seed where two runs
of one seed agreed within 1%.  Token ids and the per-request sampling
seeds come from the seed.

Keys of a mix file:

* ``burst_size``: requests per burst.
* ``prompt``, ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length distribution (tokens).
* ``document`` (optional): tokens of one shared document.  Set-up serves
  it once so the prefix cache holds it; every prompt is then the
  document followed by its own ``prompt``-sized question.
* ``greedy_every``: every n-th request of a burst decodes greedily (the
  correctness check compares those); the others sample at
  ``temperature``.
* ``serve``: cell-specific ``ServeConfig`` fields (``max_seq``,
  ``max_pages``) laid over the configuration's.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # int32 token ids
    n_out: int
    temperature: float
    sample_seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def load_mix(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def longest_request(mix: dict) -> int:
    """Longest prompt plus output any request of ``mix`` can have."""
    doc = mix.get("document", {}).get("tokens", 0)
    return doc + mix["prompt"]["max"] + mix["output"]["max"]


def stratified_lengths(spec: dict, n: int) -> List[int]:
    """The ``n`` lognormal quantiles at ``(i + 0.5) / n``, clipped to
    ``[min, max]`` and rounded, in ascending order."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    z = statistics.NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


class Generator:
    """Bursts of requests for one mix, one vocabulary and one seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        n = mix["burst_size"]
        order = np.random.default_rng(0)  # the mix's own, not the seed's
        self._prompt_lens = [stratified_lengths(mix["prompt"], n)[i]
                             for i in order.permutation(n)]
        self._out_lens = [stratified_lengths(mix["output"], n)[i]
                          for i in order.permutation(n)]
        doc = mix.get("document")
        self.document: Optional[np.ndarray] = (
            self._tokens(doc["tokens"]) if doc else None
        )

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n, dtype=np.int64).astype(np.int32)

    def burst(self) -> List[Request]:
        mix = self.mix
        n = mix["burst_size"]
        every = mix.get("greedy_every", 0)
        out = []
        for i in range(n):
            prompt = self._tokens(self._prompt_lens[i])
            if self.document is not None:
                prompt = np.concatenate([self.document, prompt])
            greedy = bool(every) and i % every == 0
            out.append(Request(
                prompt=prompt,
                n_out=self._out_lens[i],
                temperature=0.0 if greedy else float(mix["temperature"]),
                sample_seed=int(self.rng.integers(0, 2**31 - 1)),
            ))
        return out
