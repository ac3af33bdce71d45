"""Pipeline parallelism over the 'pp' mesh axis: GPipe + 1F1B schedules.

Reference analog: fleet/meta_parallel/pipeline_parallel.py:31
(PipelineParallel.train_batch) and :228 (_forward_backward_pipeline — the
1F1B steady state over NCCL p2p send/recv with SendRecvMeta handshakes)
and pp_layers.py:209 (PipelineLayer segmenting python Layers per stage).

TPU-native: the layer stack is an array axis sharded over 'pp'; the
schedule is a lax.scan whose per-step stage handoff is ONE lax.ppermute
over the pp axis inside shard_map — XLA lowers it to ICI neighbor DMA.
Backward needs no hand-written 1B schedule: jax.grad transposes the scan +
ppermute into the reverse pipeline automatically (the whole
p2p_communication.py module collapses into the transpose rule).

Bubble math matches GPipe: T = n_micro + pp - 1 steps, bubble fraction
(pp-1)/T. Invalid (bubble) steps compute garbage that is masked out of the
collected outputs — wasted FLOPs equal to the bubble, same as the
reference's idle stages.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..profiler import trace as _trace

__all__ = ["pipeline_forward", "pipeline_loss_fn",
           "pipeline_1f1b_value_and_grad",
           "pipeline_interleaved_forward", "pipeline_interleaved_loss_fn"]


def pipeline_forward(cfg, mesh, n_micro, params, ids, cp_axis=None):
    """ids -> (hidden_states [B,S,H], aux) with the decoder stack pipelined
    over 'pp'. Embedding and head stay in the GSPMD (auto) region.

    cp_axis: also shard the SEQUENCE over this mesh axis inside the
    pipeline region and run axis-level ring attention per stage —
    context parallelism composed with pipeline parallelism (the
    long-context regime the reference never shipped: each stage holds
    S/n_sp of every microbatch's activations and rotates K/V blocks
    around the sp ring while activations hop the pp ring)."""
    from ..models.llama import _rope_tables, run_layer_stack

    B, S = ids.shape
    sin, cos = _rope_tables(cfg, S)
    x = jnp.take(params["embed"], ids, axis=0)         # [B, S, H]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    x_mb = x.reshape(n_micro, mb, S, x.shape[-1])
    layers = params["layers"]

    def stage_body(layers_local, x_stack, sin_, cos_):
        n_stages = lax.axis_size("pp")
        stage = lax.axis_index("pp")

        def step(carry, t):
            state, outputs, aux = carry
            idx0 = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(stage == 0, x_stack[idx0], state)
            out, a = run_layer_stack(cfg, layers_local, inp, sin_, cos_,
                                     cp_axis=cp_axis,
                                     cp_axis_level=cp_axis is not None)
            out_idx = t - (n_stages - 1)
            valid_out = (stage == n_stages - 1) & (out_idx >= 0)
            upd = lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(out_idx, 0, n_micro - 1), 0)
            outputs = jnp.where(valid_out, upd, outputs)
            valid_compute = (t >= stage) & (t < stage + n_micro)
            aux = aux + jnp.where(valid_compute, a, 0.0)
            state = lax.ppermute(
                out, "pp",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (state, outputs, aux), None

        carry0 = (jnp.zeros_like(x_stack[0]), jnp.zeros_like(x_stack),
                  jnp.zeros((), jnp.float32))
        (state, outputs, aux), _ = lax.scan(
            step, carry0, jnp.arange(n_micro + n_stages - 1))
        # replicate the last stage's result across pp (loss/head computed
        # in the auto region). aux: stages hold disjoint layer slices
        # (sum over pp), microbatches each contribute a full-batch-mean
        # quantity (divide by n_micro to match loss_fn/1F1B), and cp
        # shards each hold a token-normalized mean (pmean over sp, not
        # psum — a sum would scale the load-balance loss by n_sp)
        outputs = lax.psum(
            jnp.where(stage == n_stages - 1, outputs,
                      jnp.zeros_like(outputs)), "pp")
        aux = lax.psum(aux, "pp") / n_micro
        if cp_axis is not None:
            aux = lax.pmean(aux, cp_axis)
        return outputs, aux

    layer_manual_specs = jax.tree_util.tree_map(lambda a: P("pp"), layers)
    if cp_axis is None:
        x_spec, rope_spec, axes = P(), P(), {"pp"}
    else:
        # sequence dim sharded over the cp axis; rope tables slice along
        # S so each shard sees its own absolute positions
        x_spec = P(None, None, cp_axis, None)
        rope_spec = P(cp_axis, None)
        axes = {"pp", cp_axis}
    outputs, aux = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(layer_manual_specs, x_spec, rope_spec, rope_spec),
        out_specs=(x_spec, P()),
        axis_names=axes, check_vma=False)(layers, x_mb, sin, cos)
    h = outputs.reshape(B, S, x.shape[-1])
    return h, aux


def _head_loss(cfg, params, h, labels, aux):
    """Shared norm/lm_head/CE epilogue for every pipelined forward."""
    from ..models.llama import rms_norm

    h = rms_norm(h, params["norm_f"], cfg.rms_norm_eps)
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - tgt)
    return ce + 0.01 * aux, ce


def pipeline_loss_fn(cfg, mesh, n_micro, params, batch, cp_axis=None):
    """Full pipelined loss (used by models.llama.build_train_step)."""
    h, aux = pipeline_forward(cfg, mesh, n_micro, params,
                              batch["input_ids"], cp_axis=cp_axis)
    return _head_loss(cfg, params, h, batch["labels"], aux)


# ---------------------------------------------------------------------------
# 1F1B schedule
# ---------------------------------------------------------------------------

def pipeline_1f1b_value_and_grad(cfg, mesh, n_micro, params, batch,
                                 overlap=False):
    """Hand-scheduled 1F1B: returns (loss, ce, grads) directly.

    Reference analog: pipeline_parallel.py:228 (_forward_backward_pipeline
    — warmup forwards, steady 1F1B, cooldown backwards, capping in-flight
    activations at O(pp) instead of GPipe's O(n_micro)).

    TPU-native: one lax.scan of T ticks inside shard_map. Per tick every
    stage runs one forward unit (activation handed to the next stage by
    ppermute) and one backward unit (gradient handed to the previous
    stage by the reverse ppermute). The backward unit re-derives its vjp
    from a ring buffer of saved *stage inputs* — activation
    recomputation, so saved state per stage is O(pp) microbatch inputs
    regardless of n_micro, while grad-of-GPipe keeps residuals for every
    scan step. jax.grad's scan transpose is replaced by explicit
    per-unit jax.vjp, so this function computes its own grads (it is not
    meant to be differentiated).

    Two schedules (arithmetic shared with ``distributed.overlap`` so the
    static simulator and this kernel cannot drift):

    * ``overlap=False`` (lockstep): F(s,m) at tick s+m, B(s,m) at tick
      2*pp-1-s+m, T = n_micro + 2*pp - 1. The ppermute at the end of
      each tick feeds the consuming compute of the very next tick —
      every stage-boundary transfer serializes against compute.
    * ``overlap=True`` (double-buffered p2p): F(s,m) at tick 2s+m,
      B(s,m) at tick 4*(pp-1)+1-2s+m, T = n_micro + 4*pp - 3. Each
      stage keeps send/recv edge buffers in the carry and issues both
      ppermutes at the *top* of the tick on values computed a full tick
      earlier, so within any tick the transfers have no data dependence
      on that tick's forward/backward units — XLA's latency-hiding
      scheduler overlaps the ICI hop with the matmuls. The price is a
      deeper warmup (2 ticks/stage) and a 4*pp ring buffer; per-edge
      numerics are identical (same units, same accumulation order).

    The CE head runs per-microbatch inside the last stage's backward unit
    (its vjp seeds the gradient chain). The embedding lives inside the
    manual region too: stage 0 looks its microbatch up per forward unit
    (ids are int32 — tiny) and accumulates d_embed as a param-sized [V,H]
    carry per backward unit, so no O(B*S*H) activation or gradient stack
    is ever materialized — per-stage live state really is the ring
    buffer plus param-sized accumulators.
    """
    from ..models.llama import _rope_tables, rms_norm, run_layer_stack
    from .overlap import schedule_constants

    # host-side build marker (the scan body itself is opaque to the
    # flight recorder — measured overlap comes from the recorded
    # schedule, see trace.record_pipeline_schedule)
    _trace.event("pipeline/build", kind="pipeline_build",
                 pp=int(mesh.shape["pp"]), n_micro=int(n_micro),
                 overlap=bool(overlap))

    ids, labels = batch["input_ids"], batch["labels"]
    B, S = ids.shape
    H = params["embed"].shape[1]
    sin, cos = _rope_tables(cfg, S)
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    ids_mb = ids.reshape(n_micro, mb, S)
    lab_mb = labels.reshape(n_micro, mb, S)
    layers = params["layers"]
    inv_nm = 1.0 / n_micro

    def stage_body(layers_local, embed_w, ids_stack, lab_stack, norm_w,
                   head_w, sin_, cos_):
        pp = lax.axis_size("pp")
        stage = lax.axis_index("pp")
        is_last = stage == pp - 1
        # pp is static under shard_map; T/BUF shared with the simulator
        consts = schedule_constants(int(pp), n_micro, overlap=overlap)
        BUF, T = consts["BUF"], consts["T"]

        def stage_fwd(ll, xin):
            return run_layer_stack(cfg, ll, xin, sin_, cos_)  # (y, aux)

        def head_ce(nw, hw, y, lab):
            h = rms_norm(y, nw, cfg.rms_norm_eps)
            logits = (h @ hw).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, lab[..., None], -1)[..., 0]
            return jnp.mean(lse - tgt)

        fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
        bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]

        def tick(carry, t):
            (fwd_state, bwd_state, fwd_recv, bwd_recv, xs_buf, dlayers,
             dembed, dnorm, dhead, ce_sum, aux_sum) = carry

            if overlap:
                # p2p issued FIRST, on edge values computed a full tick
                # earlier: no data dependence on this tick's compute, so
                # the collective-permute rides under the matmuls below.
                # fwd_state/bwd_state hold last tick's outputs (pending
                # send); fwd_recv/bwd_recv hold what arrived last tick
                # (consumed this tick).
                recv_f = lax.ppermute(fwd_state, "pp", fwd_perm)
                recv_b = lax.ppermute(bwd_state, "pp", bwd_perm)
                fwd_in, bwd_in = fwd_recv, bwd_recv
                fm = t - 2 * stage
                bm = t - (4 * (pp - 1) + 1 - 2 * stage)
            else:
                fwd_in, bwd_in = fwd_state, bwd_state
                fm = t - stage                      # F(s, m) at t = s + m
                bm = t - (2 * pp - 1 - stage)       # B(s, m)

            # ---- forward unit
            do_f = (fm >= 0) & (fm < n_micro)
            fidx = jnp.clip(fm, 0, n_micro - 1)
            x_emb = jnp.take(embed_w, ids_stack[fidx], axis=0)
            x_in = jnp.where(stage == 0, x_emb, fwd_in)
            y, _ = stage_fwd(layers_local, x_in)
            xs_upd = lax.dynamic_update_index_in_dim(
                xs_buf, x_in, fm % BUF, 0)
            xs_buf = jnp.where(do_f, xs_upd, xs_buf)

            # ---- backward unit
            do_b = (bm >= 0) & (bm < n_micro)
            bidx = jnp.clip(bm, 0, n_micro - 1)
            x_saved = xs_buf[bm % BUF]
            (y_b, aux_b), stage_vjp = jax.vjp(
                stage_fwd, layers_local, x_saved)
            ce_m, head_vjp = jax.vjp(
                lambda nw, hw, yy: head_ce(nw, hw, yy, lab_stack[bidx]),
                norm_w, head_w, y_b)
            dnorm_m, dhead_m, g_last = head_vjp(jnp.float32(inv_nm))
            g_in = jnp.where(is_last, g_last, bwd_in)
            dlayers_m, dx_m = stage_vjp(
                (g_in, jnp.asarray(0.01 * inv_nm, aux_b.dtype)))

            mask_b = do_b
            dlayers = jax.tree_util.tree_map(
                lambda acc, d: acc + jnp.where(mask_b, d, 0),
                dlayers, dlayers_m)
            mask_last = mask_b & is_last
            dnorm = dnorm + jnp.where(mask_last, dnorm_m, 0)
            dhead = dhead + jnp.where(mask_last, dhead_m, 0)
            ce_sum = ce_sum + jnp.where(mask_last, ce_m * inv_nm, 0.0)
            aux_sum = aux_sum + jnp.where(mask_b, aux_b * inv_nm, 0.0)
            # embedding backward: param-sized scatter-add on stage 0 —
            # no [n_micro, mb, S, H] gradient stack in the carry
            demb_m = jnp.zeros_like(dembed).at[ids_stack[bidx]].add(
                dx_m.astype(dembed.dtype))
            dembed = dembed + jnp.where(mask_b & (stage == 0), demb_m, 0)

            if overlap:
                # this tick's outputs become next tick's sends; this
                # tick's arrivals are consumed the tick after
                fwd_state, fwd_recv = y, recv_f
                bwd_state, bwd_recv = dx_m, recv_b
            else:
                fwd_state = lax.ppermute(y, "pp", fwd_perm)
                bwd_state = lax.ppermute(dx_m, "pp", bwd_perm)

            return (fwd_state, bwd_state, fwd_recv, bwd_recv, xs_buf,
                    dlayers, dembed, dnorm, dhead, ce_sum, aux_sum), None

        z = jnp.zeros((mb, S, H), embed_w.dtype)
        carry0 = (
            z, z, z, z, jnp.zeros((BUF, mb, S, H), embed_w.dtype),
            jax.tree_util.tree_map(jnp.zeros_like, layers_local),
            jnp.zeros_like(embed_w),
            jnp.zeros_like(norm_w), jnp.zeros_like(head_w),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        (fwd_state, bwd_state, fwd_recv, bwd_recv, xs_buf, dlayers,
         dembed, dnorm, dhead, ce_sum, aux_sum), _ = lax.scan(
            tick, carry0, jnp.arange(T))

        # head/embed grads and the scalars live on one stage; psum
        # replicates them so out_specs can be P()
        dembed = lax.psum(dembed, "pp")
        dnorm = lax.psum(dnorm, "pp")
        dhead = lax.psum(dhead, "pp")
        ce_sum = lax.psum(ce_sum, "pp")
        aux_sum = lax.psum(aux_sum, "pp")
        return dlayers, dembed, dnorm, dhead, ce_sum, aux_sum

    layer_manual_specs = jax.tree_util.tree_map(lambda a: P("pp"), layers)
    dlayers, dembed, dnorm, dhead, ce, aux = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(layer_manual_specs, P(), P(), P(), P(), P(), P(), P()),
        out_specs=(layer_manual_specs, P(), P(), P(), P(), P()),
        axis_names={"pp"}, check_vma=False)(
            layers, params["embed"], ids_mb, lab_mb, params["norm_f"],
            params["lm_head"], sin, cos)

    grads = {"embed": dembed, "layers": dlayers, "norm_f": dnorm,
             "lm_head": dhead}
    loss = ce + 0.01 * aux
    return loss, ce, grads


# ---------------------------------------------------------------------------
# interleaved (virtual-stage) schedule
# ---------------------------------------------------------------------------

def pipeline_interleaved_forward(cfg, mesh, n_micro, v, params, ids):
    """Circular interleaved pipeline: each device holds ``v`` layer
    chunks (virtual stages), cutting the bubble fraction from
    (pp-1)/(m+pp-1) to roughly (pp-1)/(v*m+pp-1).

    Reference analog: pipeline_parallel.py:461
    (_forward_backward_pipeline with virtual_pp_degree — the interleaved
    1F1B schedule over chunked PipelineLayer segments).

    TPU-native: global stage g = chunk*pp + device. Microbatches stream
    in groups of pp (the reference's n_micro % pp == 0 constraint, made
    exact as group size = pp) through ONE fused scan of
    T = n_micro*v + pp - 1 ticks: work index r = t - device decomposes
    into (group, chunk, micro), every device executes exactly one unit
    per tick, and the hand-off g -> g+1 is the same neighbor ppermute as
    GPipe — when device pp-1 wraps to device 0 the receiver just indexes
    its next chunk. The drain bubble is paid once per batch, giving the
    (pp-1)/(v*m + pp-1) fraction above. Backward is jax.grad's transpose
    of the scan, as in the GPipe path.
    """
    from ..models.llama import _rope_tables, run_layer_stack

    import numpy as np

    B, S = ids.shape
    sin, cos = _rope_tables(cfg, S)
    x = jnp.take(params["embed"], ids, axis=0)
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    H = x.shape[-1]
    layers = params["layers"]
    L = jax.tree_util.tree_leaves(layers)[0].shape[0]

    # device d's pp-shard is a CONTIGUOUS layer block, but global stage
    # g = c*pp + d must hold layer chunk g: permute chunks so local
    # position (d, c) carries global chunk c*pp + d (grad transposes the
    # gather back automatically)
    pp_deg = dict(zip(mesh.axis_names,
                      np.asarray(mesh.devices).shape))["pp"]
    n_chunks = pp_deg * v
    assert L % n_chunks == 0, (L, pp_deg, v)
    perm = jnp.asarray([c * pp_deg + d for d in range(pp_deg)
                        for c in range(v)])

    def _reorder(a):
        ck = a.reshape(n_chunks, a.shape[0] // n_chunks, *a.shape[1:])
        return ck[perm].reshape(a.shape)

    layers = jax.tree_util.tree_map(_reorder, layers)

    def stage_body(layers_local, x_stack, sin_, cos_):
        pp = lax.axis_size("pp")
        d = lax.axis_index("pp")
        assert n_micro % pp == 0, (n_micro, pp)
        k_groups = n_micro // pp
        # layers_local: [L/pp, ...] -> [v, L/(pp*v), ...] virtual chunks
        chunked = jax.tree_util.tree_map(
            lambda a: a.reshape(v, a.shape[0] // v, *a.shape[1:]),
            layers_local)
        # ONE fused scan over all groups: work index r = t - d
        # decomposes as (group, chunk, micro) = (r//(v*pp), (r%(v*pp))
        # //pp, r%pp); groups stream back-to-back so the (pp-1)-tick
        # drain bubble is paid once per batch, not once per group
        T = k_groups * v * pp + pp - 1

        def tick(carry, t):
            state, outputs, aux = carry
            r = t - d
            active = (r >= 0) & (r < k_groups * v * pp)
            rr = jnp.clip(r, 0, k_groups * v * pp - 1)
            gi = rr // (v * pp)
            c = (rr % (v * pp)) // pp               # virtual chunk
            m_global = gi * pp + (rr % pp)          # micro index
            is_entry = (d == 0) & (c == 0)
            x_in = jnp.where(is_entry, x_stack[m_global], state)
            chunk_layers = jax.tree_util.tree_map(
                lambda a: a[c], chunked)
            y, a = run_layer_stack(cfg, chunk_layers, x_in, sin_, cos_)
            aux = aux + jnp.where(active, a, 0.0)
            is_exit = (d == pp - 1) & (c == v - 1) & active
            upd = lax.dynamic_update_index_in_dim(outputs, y, m_global, 0)
            outputs = jnp.where(is_exit, upd, outputs)
            state = lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)])
            return (state, outputs, aux), None

        carry0 = (jnp.zeros((mb, S, H), x_stack.dtype),
                  jnp.zeros((n_micro, mb, S, H), x_stack.dtype),
                  jnp.zeros((), jnp.float32))
        (_, outputs, aux), _ = lax.scan(tick, carry0, jnp.arange(T))
        outputs = lax.psum(
            jnp.where(d == pp - 1, outputs, jnp.zeros_like(outputs)),
            "pp")
        aux = lax.psum(aux, "pp")
        return outputs, aux

    layer_manual_specs = jax.tree_util.tree_map(lambda a: P("pp"), layers)
    x_mb = x.reshape(n_micro, mb, S, H)
    outputs, aux = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(layer_manual_specs, P(), P(), P()),
        out_specs=(P(), P()),
        axis_names={"pp"}, check_vma=False)(layers, x_mb, sin, cos)
    h = outputs.reshape(B, S, H)
    return h, aux


def pipeline_interleaved_loss_fn(cfg, mesh, n_micro, v, params, batch):
    """Interleaved-schedule loss (build_train_step schedule
    "interleaved")."""
    h, aux = pipeline_interleaved_forward(cfg, mesh, n_micro, v, params,
                                          batch["input_ids"])
    return _head_loss(cfg, params, h, batch["labels"], aux)
