"""The two layouts of one serve step's tokens.

The engine hands a step ``tokens [R, Tc]``: row r (an engine slot) feeds
``tokens[r, :q_lens[r]]`` and the rest is padding.  What needs a row's
structure — the K/V write and the paged attention, a recurrent layer's
convolution and scan — works on that padded ``[R, Tc, ...]`` layout.  What
is per token — embedding, norms, every projection, the MLP, the head, the
argmax — does not, and runs on a flat batch ``[T, ...]`` of the step's fed
tokens, row after row: padded ``(r, t)`` with ``t < q_lens[r]`` is flat
``start[r] + t``, ``start = cumsum(q_lens) - q_lens``.  Everything past the
fed tokens, in either layout, is zero: a position that holds no token never
carries another row's values.

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
    ``[T]`` batch of fed tokens, built from ``q_lens [R]`` while tracing."""

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
        if not self.compact:
            self.last = (jnp.arange(R, dtype=jnp.int32) * self.Tc
                         + jnp.clip(q - 1, 0, self.Tc - 1))
            return
        ends = jnp.cumsum(q)
        start = ends - q
        i = jnp.arange(self.T, dtype=jnp.int32)
        # the row of flat token i: how many rows end at or before it
        row = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), R - 1)
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
        in memory: what reads the rows (the scan, the convolution) takes one
        position of all rows at a time, a contiguous slab this way.  Gathered
        row-major, the Jamba step paid 3.3 ms for ``dt * x`` and 3.3 ms for
        the stack of the scan's outputs in full passes that the padded
        program never makes (PERF.md section 6, PR 30)."""
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
