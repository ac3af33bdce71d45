"""The two layouts of one serve step's tokens.

The engine hands a step ``tokens [R, Tc]``: row r (an engine slot) feeds
``tokens[r, :q_lens[r]]`` and the rest is padding.  What takes a row's
positions as one block — the K/V write and the paged attention — works on
that padded ``[R, Tc, ...]`` layout.  What is per token — embedding, norms,
every projection, the MLP, the head, the argmax — does not, and runs on a
flat batch ``[T, ...]`` of the step's fed tokens, row after row: padded
``(r, t)`` with ``t < q_lens[r]`` is flat ``start[r] + t``, ``start =
cumsum(q_lens) - q_lens``.  A recurrent layer's convolution and scan need a
row's positions in order and stay flat too: a row's tokens lie one after
another, so they read them where they lie, by ``start``, ``row`` and ``pos``
(since PR 38; padded ``[R x Tc, E]`` copies a layer were a fifth of the
Jamba step).  Everything past the fed tokens, in either layout, is zero: a
position that holds no token never carries another row's values.

``StepLayout(q_lens, Tc)`` is the identity (``T = R x Tc``, both maps are
reshapes: the padded program).  ``StepLayout(q_lens, Tc, T)`` computes ``T``
positions; the caller guarantees ``sum(q_lens) <= T`` (the scheduler's token
budget, checked by the engine on the host: tokens past ``T`` would vanish).
The maps follow from ``q_lens`` inside the program, so a step uploads
nothing for them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["StepLayout"]

# the name of every operation of this module in a profile (metadata only):
# ``layout_ms_per_step`` reads the device time under it
SCOPE = "step_layout"


class StepLayout:
    """Index maps between a step's padded ``[R, Tc]`` positions and its flat
    ``[T]`` batch of fed tokens, built from ``q_lens [R]`` while tracing.

    For what walks the flat batch by rows: ``start``, ``ends [R]`` (row r's
    tokens are flat ``start[r] .. ends[r] - 1``), ``row``, ``pos [T]`` (flat
    token i is position ``pos[i]`` of row ``row[i]``) and ``valid [T]`` (i
    holds a fed token); ``of_rows`` and ``last_tokens`` move between a
    per-row array and the flat batch."""

    def __init__(self, q_lens, Tc: int, step_tokens: Optional[int] = None):
        R = q_lens.shape[0]
        self.R, self.Tc = R, int(Tc)
        self.compact = step_tokens is not None
        self.T = int(step_tokens) if self.compact else R * self.Tc
        with jax.named_scope(SCOPE):
            self._index(q_lens.astype(jnp.int32))

    def _index(self, q):
        R = self.R
        t = jnp.arange(self.Tc, dtype=jnp.int32)
        i = jnp.arange(self.T, dtype=jnp.int32)
        if not self.compact:
            self.start = jnp.arange(R, dtype=jnp.int32) * self.Tc
            self.ends = self.start + jnp.minimum(q, self.Tc)
            self.row, self.pos = i // self.Tc, i % self.Tc
            self.valid = self.pos < q[self.row]
            self.last = self.start + jnp.clip(q - 1, 0, self.Tc - 1)
            return
        ends = jnp.cumsum(q)
        start = ends - q
        # the row of flat token i: how many rows end at or before it
        row = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), R - 1)
        self.start, self.ends, self.row = start, ends, row
        self.pos, self.valid = i - start[row], i < ends[-1]
        # flat <- padded and padded <- flat, as indices into the other
        # layout's leading axis; one past its end reads as zero.  The
        # padded side is indexed position-major, [Tc, R]: see ``rows``
        self._src = jnp.where(i < ends[-1], (i - start[row]) * R + row,
                              R * self.Tc)
        self._dst = jnp.where(t[:, None] < q[None, :],
                              start[None, :] + t[:, None], self.T)
        # the same two maps for a padded side kept row-major, [R, Tc]
        self._src_rows = jnp.where(i < ends[-1],
                                   row * self.Tc + i - start[row],
                                   R * self.Tc)
        self.last = jnp.clip(ends - 1, 0, self.T - 1)

    def flat(self, x, row_major=False):
        """``x [R, Tc, ...]`` as ``[T, ...]``: the fed tokens, then zeros.
        ``row_major``: ``x`` lies row after row in memory (a kernel wrote
        it so) and is gathered as it lies; the default takes it
        position-major, as ``rows`` leaves an array."""
        with jax.named_scope(SCOPE):
            if not self.compact:
                return x.reshape((self.R * self.Tc,) + x.shape[2:])
            if row_major:
                return jnp.take(
                    x.reshape((self.R * self.Tc,) + x.shape[2:]),
                    self._src_rows, axis=0, mode="fill", fill_value=0)
            x = jnp.swapaxes(x, 0, 1).reshape(
                (self.Tc * self.R,) + x.shape[2:])
            return jnp.take(x, self._src, axis=0, mode="fill", fill_value=0)

    def rows(self, x, row_major=False):
        """``x [T, ...]`` as ``[R, Tc, ...]``: each row's tokens, then zeros
        (the identity layout keeps what its padding positions computed).
        ``row_major``: the result is gathered row after row, for a reader
        that takes one row's positions at a time (an attention kernel's
        query block); at 64 heads of 640 lanes the position-major gather and
        the transpose into such a block were 0.17 GB a sublayer moved twice
        (PERF.md section 6, PR 37).

        The gather writes ``[Tc, R, ...]`` and the result is its transpose,
        which costs nothing and tells XLA to keep the array position-major
        in memory, so that one position of all rows is a contiguous slab.
        The Mamba mixers' convolution and scan, which this order was chosen
        for (PERF.md section 6, PR 30), read the flat batch since PR 38 and
        call neither ``rows`` nor ``flat``; the attention layers keep the
        order they were measured with."""
        with jax.named_scope(SCOPE):
            if not self.compact:
                return x.reshape((self.R, self.Tc) + x.shape[1:])
            # a zero row past the flat tokens for the positions that hold
            # none: a "fill" gather would go over the padded result once more
            x = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])
            if row_major:
                return jnp.take(x, self._dst.T, axis=0, mode="clip")
            return jnp.swapaxes(
                jnp.take(x, self._dst, axis=0, mode="clip"), 0, 1)

    def of_rows(self, a):
        """``a [R, ...]`` as ``[T, ...]``: flat token i gets the entry of its
        row ``row[i]`` (a token past the fed ones that of some row: select
        it away)."""
        with jax.named_scope(SCOPE):
            if self.compact:
                return jnp.take(a, self.row, axis=0, mode="clip")
            return jnp.repeat(a, self.Tc, axis=0)

    def last_tokens(self, x, n):
        """``x [T, ...]`` as ``[n, R, ...]``: entry m is row r's token ``n -
        m`` before its end, flat ``ends[r] - n + m`` (for a row of fewer
        tokens some other token: select it away)."""
        with jax.named_scope(SCOPE):
            back = jnp.arange(-n, 0, dtype=jnp.int32)[:, None]
            if self.compact:                  # (clip: under 0 reads token 0)
                return jnp.take(x, self.ends[None, :] + back, axis=0,
                                mode="clip")
            # padded rows: a select a position, no gather (none at Tc = 1)
            rows = x.reshape((self.R, self.Tc) + x.shape[1:])
            at = (self.ends - self.start)[None, :] + back        # [n, R]
            at = at.reshape(at.shape + (1,) * (x.ndim - 1))
            out = jnp.broadcast_to(rows[:, 0], (n,) + rows[:, 0].shape)
            for t in range(1, self.Tc):
                out = jnp.where(at == t, rows[:, t], out)
            return out
