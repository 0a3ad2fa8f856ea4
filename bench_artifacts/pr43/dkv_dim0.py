"""The `%flash_dkv` body NOT chosen (PR 43, call k1's side `change`): the
parent's form with the operands as given, `p^T dO` and `ds^T q` contracting
dimension 0 of both operands. On the chip Mosaic transposes the `[Bq, Bk]`
blocks for it (1,235.7 us a call at `[14, 8192, 64]` where the transposed
scores the module has now take 848.6). `flash_pair.py` puts `kernel(mod)` in
`mod._bwd_dkv_kernel`'s place as its side `dkv_dim0`."""

import jax
import jax.numpy as jnp


def kernel(mod):
    """`mod._bwd_dkv_kernel` with the query-major `_compute`; `mod` is a
    loaded `areal_tpu/ops/flash_attention.py`."""
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))  # a b^T
    tn = (((0,), (0,)), ((), ()))  # a^T b

    def _bwd_dkv_kernel(*refs, sm_scale: float):
        sched = refs[:mod._N_SCHED]
        (seg_k_ref, kpos_ref, k_ref, v_ref, idq_hbm, q_hbm, do_hbm, rows_hbm,
         dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, idq_buf, q_buf, do_buf, rows_buf,
         sems) = refs[mod._N_SCHED:]

        def copies(b, h, col, buf):
            srcs = (idq_hbm.at[b, col], q_hbm.at[b, h, col], do_hbm.at[b, h, col],
                    rows_hbm.at[b, h, col])
            return mod._copies(srcs, (idq_buf, q_buf, do_buf, rows_buf), sems, buf)

        hd = k_ref.shape[-1]

        def _init():
            dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
            dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

        def _compute(buf):
            q = q_buf[buf, :, :hd]
            k = k_ref[0, 0]
            v = v_ref[0, 0]
            do = do_buf[buf, :, :hd]
            lse = rows_buf[buf, mod._LSE][:, None]  # [Bq, 1]
            delta = rows_buf[buf, mod._DELTA][:, None]
            dlse = rows_buf[buf, mod._DLSE][:, None]
            mask = mod._mask_for(idq_buf[buf, 0], seg_k_ref[0, 0], idq_buf[buf, 1], kpos_ref[0, 0])
            s = mod._scores(q, k, mask, sm_scale)  # [Bq, Bk]
            p = jnp.exp(s - lse)
            p = jnp.where(lse > mod._NEG_INF / 2, p, 0.0)
            dv_acc_ref[:] += jax.lax.dot_general(
                p.astype(do.dtype), do, tn, preferred_element_type=f32)
            dp = jax.lax.dot_general(do, v, nt, preferred_element_type=f32)
            ds = p * (dp - delta + dlse)
            dk_acc_ref[:] += sm_scale * jax.lax.dot_general(
                ds.astype(q.dtype), q, tn, preferred_element_type=f32)

        def _finalize():
            dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)

        def _no_query():
            dk_ref[...] = jnp.zeros_like(dk_ref)
            dv_ref[...] = jnp.zeros_like(dv_ref)

        mod._walk(sched, copies, _compute, _init, _finalize, _no_query)

    return _bwd_dkv_kernel
