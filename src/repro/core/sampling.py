"""Seeded categorical sampling — the ONE sampler every serving path runs.

``sample_tokens`` is shared verbatim by the stepped engine, the one-shot
batched engine, the continuous mixed step, and the fused decode loop
(``lm.paged_decode_loop``), so "fused == stepped" for sampled output is
a property of call-site plumbing, not of four implementations agreeing.

Reproducibility contract (docs/serving.md "Sampling"):

* Per-row PRNG keys derive from ``(request seed, fed-stream position)``
  — ``fold_in(PRNGKey(seed), position)`` where ``position`` is the
  absolute position of the token whose logits are being sampled (the
  last fed token).  Output token ``g_i`` is always sampled at position
  ``s0 - 1 + i`` regardless of batch slot, scheduler iteration,
  ``decode_block``, or how often the request was preempted — so sampled
  output is batch-invariant, fused-run-invariant, and byte-identical
  across preempt-and-recompute replays (replays re-feed the stream
  without sampling; post-replay samples land on the same positions and
  therefore the same keys).
* ``temperature == 0`` short-circuits to plain argmax over the raw
  logits — bit-for-bit the pre-sampling greedy path (a ``lax.cond``
  skips the sampling math entirely when no row samples, so greedy
  serving also pays no sampling cost).
* ``top_k`` keeps the k highest logits (``None``/0 disables), then
  ``top_p`` keeps the smallest set of tokens whose cumulative
  probability reaches ``top_p`` (nucleus); the filtered distribution is
  drawn via ``jax.random.categorical``.  All of it is elementwise /
  per-row math, so co-batched rows never couple.

The module lives in ``repro.core`` (not ``repro.serve``) because
``models/lm.py`` fuses it into the decode loop and must not import the
serving stack.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

# on-device encoding for "no top-k filter" (SamplingParams uses None)
TOP_K_DISABLED = 0


def validate_sampling(temperature, top_k, top_p, seed=0, where="sampling"):
    """Reject malformed sampling knobs loudly at construction time."""
    t = float(temperature)
    if math.isnan(t) or math.isinf(t) or t < 0:
        raise ValueError(
            f"{where}: temperature must be finite and >= 0, got {temperature!r}"
        )
    if top_k is not None:
        if int(top_k) != top_k or int(top_k) < 1:
            raise ValueError(
                f"{where}: top_k must be an int >= 1 (or None to disable), "
                f"got {top_k!r}"
            )
    p = float(top_p)
    if math.isnan(p) or not (0.0 < p <= 1.0):
        raise ValueError(
            f"{where}: top_p must satisfy 0 < top_p <= 1, got {top_p!r}"
        )
    if int(seed) != seed or int(seed) < 0:
        raise ValueError(
            f"{where}: seed must be an int >= 0, got {seed!r}"
        )


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (host-side, validated).

    ``temperature=0`` is exact greedy argmax; ``top_k=None`` disables the
    top-k filter; ``top_p=1.0`` disables nucleus filtering; ``seed`` is
    the base PRNG seed the per-position keys fold into.
    """

    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        validate_sampling(
            self.temperature, self.top_k, self.top_p, self.seed,
            where="SamplingParams",
        )

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def _sample_row(logits, temp, top_k, top_p, seed, pos):
    """Draw one token from one row of raw logits (f32 math throughout)."""
    v = logits.shape[-1]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
    scaled = logits.astype(jnp.float32) / jnp.where(temp > 0, temp, 1.0)
    # top-k: threshold at the k-th largest scaled logit (0 = disabled);
    # ties at the threshold are all kept, deterministically
    k = jnp.where(top_k > 0, jnp.minimum(top_k, v), v)
    desc = jnp.sort(scaled)[::-1]
    masked = jnp.where(scaled < desc[k - 1], -jnp.inf, scaled)
    # top-p (nucleus) over the top-k survivors: keep the smallest prefix
    # of the probability-sorted tokens whose cumulative mass reaches p
    # (always at least one token; re-masking `masked` keeps the top-k
    # cut — a threshold prob of 0 cannot resurrect filtered entries)
    probs = jax.nn.softmax(masked)
    sp = jnp.sort(probs)[::-1]
    cut = jnp.sum(jnp.cumsum(sp) < top_p)
    thr = sp[jnp.minimum(cut, v - 1)]
    # top_p == 1 keeps every token exactly: the f32 cumulative mass
    # reaches 1 a little early or late depending on how the program
    # that samples was compiled, and must not trim the tail by rounding
    masked = jnp.where((top_p < 1.0) & (probs < thr), -jnp.inf, masked)
    return jax.random.categorical(key, masked).astype(jnp.int32)


def sample_tokens(logits, temps, top_ks, top_ps, seeds, positions):
    """Sample one token per row from raw (pre-temperature) logits.

    ``logits [B, V]`` must already be sliced to the real vocab; ``temps /
    top_ks / top_ps / seeds / positions`` are ``[B]`` per-row arrays
    (``top_k`` 0 = disabled; ``positions`` is each row's fed-stream
    position — negative padding positions are clamped, their outputs are
    never read).  Rows with ``temp == 0`` return the plain argmax,
    bit-identical to the greedy path; a ``lax.cond`` skips the sampling
    math entirely when NO row samples, so greedy dispatches stay as
    cheap as before sampling existed.  Every operation is per-row, so a
    row's token never depends on what it is co-batched with.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos = jnp.maximum(positions, 0)

    def drawn(_):
        toks = jax.vmap(_sample_row)(logits, temps, top_ks, top_ps, seeds, pos)
        return jnp.where(temps > 0, toks, greedy)

    return jax.lax.cond(jnp.any(temps > 0), drawn, lambda _: greedy, None)
