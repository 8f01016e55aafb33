"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory, strictly recurrent).  [arXiv:2405.04517]

mLSTM per head, in stabilized log-space (the exponential input gate forces a
running max stabilizer ``m`` — unlike SSD whose decays are all <= 1):

    m_t = max(logsig(f_t) + m_{t-1}, i_t)
    C_t = exp(logsig(f_t) + m_{t-1} - m_t) C_{t-1} + exp(i_t - m_t) k_t v_t^T
    n_t = (same decay) n_{t-1} + exp(i_t - m_t) k_t
    h_t = (q_t C_t) / max(|q_t . n_t|, exp(-m_t))

The chunkwise form factors every within-chunk coefficient as
``exp((i_s - b_s) - g_t)`` with b = cumsum(logsig(f)), a = cummax(i - b),
g_t = max(m_prev, a_t): all exponents are <= 0, so the (Q, Q) decay matrix is
stable by construction.  Cross-chunk state (C, n, m) is carried by lax.scan.

sLSTM is the paper's strictly-sequential scalar-memory cell (one lax.scan
step per token) with block-diagonal per-head recurrence, followed by the
gated up-projection FFN.  Decode for both is the O(1) single-step recurrence.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import dtype_of
from repro.models.ssm import _causal_conv

_M_CLAMP = 60.0  # exp(60) ~ 1e26: safe in f32

# mLSTM input-gate bias at init, as in xLSTM 7B (Beck et al., 2025).  At
# 0 the normaliser |q.n| sits near its floor exp(-m) and passes it at a
# third of the positions, where h is a ratio of two linear forms in q; the
# backward then grows about 10x per 8-block super-block.  On a CPU, the
# xlstm-1.3b stack (48 blocks) cut to width 512 had a step-0 gradient
# norm of 1.4e7 at 0 and 20 at -10 (8 blocks: 9): the floor, about e^10,
# then dominates and h is linear in q, k and v.
_B_I_INIT = -10.0

# The cells' f32 contractions (the only f32 x f32 ones in the model) run
# f32-exact.  At a TPU's default precision their operands are rounded to
# bf16: on full-depth xlstm-1.3b, with the input-gate bias below at 0,
# that made the step-0 gradient norm 28x what it is with exact products
# (1.7e5 against 6.1e3 on a TPU v5e).
_einsum = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)


def mlstm_dims(cfg: ModelConfig) -> tuple[int, int, int, int]:
    x = cfg.xlstm
    d_in = int(x.proj_factor * cfg.d_model)
    h = cfg.n_heads
    dv = d_in // h
    dk = int(d_in * x.qk_dim_factor) // h
    return d_in, h, dk, dv


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def init_mlstm(cfg: ModelConfig, key: jax.Array) -> dict:
    x = cfg.xlstm
    dt = dtype_of(cfg)
    d_in, h, dk, dv = mlstm_dims(cfg)
    qk = h * dk
    ks = jax.random.split(key, 7)
    return {
        "up_proj": common.dense_init(ks[0], (cfg.d_model, d_in), dt),
        "z_proj": common.dense_init(ks[1], (cfg.d_model, d_in), dt),
        "conv": common.dense_init(ks[2], (x.conv_dim, d_in), dt,
                                  fan_in=x.conv_dim),
        "wq": common.dense_init(ks[3], (d_in, qk), dt, fan_in=d_in),
        "wk": common.dense_init(ks[4], (d_in, qk), dt, fan_in=d_in),
        "wi_gate": common.dense_init(ks[5], (d_in, h), dt, fan_in=d_in),
        "wf_gate": common.dense_init(ks[6], (d_in, h), dt, fan_in=d_in),
        "b_i": jnp.full((h,), _B_I_INIT, jnp.float32),
        "b_f": jnp.full((h,), 3.0, jnp.float32),   # start remembering
        "head_norm": jnp.ones((d_in,), jnp.float32),
        "down_proj": common.dense_init(
            jax.random.fold_in(key, 7), (d_in, cfg.d_model), dt, fan_in=d_in),
    }


def _mlstm_chunk_scan(q, k, v, ig, fg, chunk, state):
    """q/k (B,T,H,dk), v (B,T,H,dv), ig/fg (B,T,H) f32.
    state = (C (B,H,dk,dv), n (B,H,dk), m (B,H)) f32.
    Returns (h (B,T,H,dv) f32, new state)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    qc = min(chunk, t)
    nc = t // qc
    assert nc * qc == t, f"seq {t} not divisible by chunk {qc}"

    def reshape_c(x):
        return x.reshape(b, nc, qc, *x.shape[2:]).swapaxes(0, 1)

    qs, ks_, vs = reshape_c(q), reshape_c(k), reshape_c(v)
    igs, fgs = reshape_c(ig), reshape_c(fg)

    smask = (jnp.arange(qc)[:, None] >= jnp.arange(qc)[None, :])

    def body(carry, inp):
        c_prev, n_prev, m_prev = carry
        qi, ki, vi, ii, fi = inp                       # (B,Q,H,*) / (B,Q,H)
        logf = jax.nn.log_sigmoid(fi)                  # (B,Q,H)
        bcum = jnp.cumsum(logf, axis=1)
        ib = ii - bcum
        a = lax.cummax(ib, axis=1)
        g = jnp.maximum(m_prev[:, None, :], a)         # (B,Q,H)
        m_t = bcum + g

        carry_coef = jnp.exp(m_prev[:, None, :] - g)   # (B,Q,H) <= 1
        # D[t,s] = exp(ib_s - g_t), s <= t   -> (B,H,Qt,Qs)
        dmat = jnp.exp(
            ib.transpose(0, 2, 1)[:, :, None, :]
            - g.transpose(0, 2, 1)[:, :, :, None]
        )
        dmat = jnp.where(smask[None, None], dmat, 0.0)
        scores = _einsum("bthk,bshk->bhts", qi, ki)
        wmat = scores * dmat

        num = _einsum("bhts,bshd->bthd", wmat, vi)
        num = num + carry_coef[..., None] * _einsum(
            "bthk,bhkd->bthd", qi, c_prev)
        den = _einsum("bhts->bth", wmat)
        den = den + carry_coef * _einsum("bthk,bhk->bth", qi, n_prev)
        floor = jnp.exp(jnp.minimum(-m_t, _M_CLAMP))
        hout = num / jnp.maximum(jnp.abs(den), floor)[..., None]

        g_end = g[:, -1]                               # (B,H)
        u_end = jnp.exp(ib - g_end[:, None, :])        # (B,Q,H) <= 1
        coef = jnp.exp(m_prev - g_end)
        c_new = coef[..., None, None] * c_prev + _einsum(
            "bqh,bqhk,bqhd->bhkd", u_end, ki, vi)
        n_new = coef[..., None] * n_prev + _einsum(
            "bqh,bqhk->bhk", u_end, ki)
        m_new = bcum[:, -1] + g_end
        return (c_new, n_new, m_new), hout

    state_f, hs = lax.scan(body, state, (qs, ks_, vs, igs, fgs))
    h_full = hs.swapaxes(0, 1).reshape(b, t, h, dv)
    return h_full, state_f


def _mlstm_chunkwise_parallel(q, k, v, ig, fg, chunk, state):
    """Chunkwise-*parallel* mLSTM: numerically identical to
    ``_mlstm_chunk_scan`` (tested) but with all heavy einsums OUTSIDE the
    cross-chunk recurrence.

    TPU adaptation (DESIGN.md §4 / §Perf): the serial form runs the
    O(Q²·dk + Q·dk·dv) intra-chunk contractions inside a ``lax.scan`` —
    nc sequential MXU launches and an XLA cost model that counts the body
    once.  Here phase A computes per-chunk summaries for ALL chunks in
    parallel (one big batched einsum), phase B scans only the O(H·dk·dv)
    elementwise state recurrence, and phase C combines intra- and
    inter-chunk contributions in parallel.  Stabilization: all
    exponentials are taken relative to the per-chunk running max ``a`` or
    its sequential refinement ``g`` — every exp() stays <= 1 exactly as in
    the serial form.
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    qc = min(chunk, t)
    nc = t // qc
    assert nc * qc == t, f"seq {t} not divisible by chunk {qc}"

    def rc(x):  # (B,T,...) -> (B,NC,Q,...)
        return x.reshape(b, nc, qc, *x.shape[2:])

    from repro.distributed import hints
    qs, ks_, vs = rc(q), rc(k), rc(v)
    igs, fgs = rc(ig), rc(fg)                      # (B,NC,Q,H)

    # ---- phase A: per-chunk parallel quantities ---------------------------
    logf = jax.nn.log_sigmoid(fgs)
    bcum = jnp.cumsum(logf, axis=2)                # (B,NC,Q,H)
    ib = igs - bcum
    a = lax.cummax(ib, axis=2)                     # running max within chunk
    a_end = a[:, :, -1]                            # (B,NC,H)
    bcum_end = bcum[:, :, -1]

    # Stable chunk summaries relative to a_end (ib <= a_end within chunk).
    u_p = jnp.exp(ib - a_end[:, :, None])          # (B,NC,Q,H) <= 1
    # 'mlstm_chunk_state' hint (no-op without a rule): pins the per-chunk
    # state layout so the summary einsums, the cross-chunk scan and the
    # combine phase agree — without it GSPMD reshards (B,NC,H,dk,dv)
    # between phases every layer (§Roofline: the xlstm train outlier).
    u_c = hints.constrain(
        _einsum("bcqh,bcqhk,bcqhd->bchkd", u_p, ks_, vs),
        "mlstm_chunk_state")
    nu_c = _einsum("bcqh,bcqhk->bchk", u_p, ks_)

    # Intra-chunk attention-like part relative to a_t (row max).
    smask = jnp.arange(qc)[:, None] >= jnp.arange(qc)[None, :]
    dmat_p = jnp.exp(
        ib.transpose(0, 1, 3, 2)[:, :, :, None, :]       # ib_s  (B,NC,H,1,Q)
        - a.transpose(0, 1, 3, 2)[:, :, :, :, None]      # a_t   (B,NC,H,Q,1)
    )
    dmat_p = jnp.where(smask[None, None, None], dmat_p, 0.0)
    scores = _einsum("bcthk,bcshk->bchts", qs, ks_)
    wmat = scores * dmat_p                          # (B,NC,H,Q,Q)
    intra_num = _einsum("bchts,bcshd->bcthd", wmat, vs)
    intra_den = jnp.sum(wmat, axis=-1)              # (B,NC,H,Q)
    intra_den = intra_den.transpose(0, 1, 3, 2)     # (B,NC,Q,H)

    # ---- phase B: cheap cross-chunk state recurrence ----------------------
    def body(carry, inp):
        c_prev, n_prev, m_prev = carry
        ae, be, uc, nuc = inp
        g_end = jnp.maximum(m_prev, ae)             # (B,H)
        coef = jnp.exp(m_prev - g_end)
        su = jnp.exp(ae - g_end)
        c_new = coef[..., None, None] * c_prev + su[..., None, None] * uc
        n_new = coef[..., None] * n_prev + su[..., None] * nuc
        m_new = be + g_end
        return (c_new, n_new, m_new), (c_prev, n_prev, m_prev)

    xs = (jnp.moveaxis(a_end, 1, 0), jnp.moveaxis(bcum_end, 1, 0),
          jnp.moveaxis(u_c, 1, 0), jnp.moveaxis(nu_c, 1, 0))
    state_f, (c_prevs, n_prevs, m_prevs) = lax.scan(body, state, xs)
    c_prevs = hints.constrain(jnp.moveaxis(c_prevs, 0, 1),
                              "mlstm_chunk_state")  # (B,NC,H,dk,dv)
    n_prevs = jnp.moveaxis(n_prevs, 0, 1)           # (B,NC,H,dk)
    m_prevs = jnp.moveaxis(m_prevs, 0, 1)           # (B,NC,H)

    # ---- phase C: parallel combine ----------------------------------------
    g = jnp.maximum(m_prevs[:, :, None], a)         # (B,NC,Q,H)
    m_t = bcum + g
    r = jnp.exp(a - g)                              # row rescale <= 1
    carry_coef = jnp.exp(m_prevs[:, :, None] - g)   # (B,NC,Q,H)
    inter_num = _einsum("bcqhk,bchkd->bcqhd", qs, c_prevs)
    num = r[..., None] * intra_num + carry_coef[..., None] * inter_num
    inter_den = _einsum("bcqhk,bchk->bcqh", qs, n_prevs)
    den = r * intra_den + carry_coef * inter_den
    floor = jnp.exp(jnp.minimum(-m_t, _M_CLAMP))
    hout = num / jnp.maximum(jnp.abs(den), floor)[..., None]
    return hout.reshape(b, t, h, dv), state_f


def init_mlstm_state(cfg: ModelConfig, batch: int) -> dict:
    x = cfg.xlstm
    d_in, h, dk, dv = mlstm_dims(cfg)
    return {
        "C": jnp.zeros((batch, h, dk, dv), jnp.float32),
        "n": jnp.zeros((batch, h, dk), jnp.float32),
        "m": jnp.full((batch, h), -_M_CLAMP, jnp.float32),
        "conv": jnp.zeros((batch, x.conv_dim - 1, d_in), dtype_of(cfg)),
    }


def _mlstm_project(cfg, p, x, conv_tail):
    from repro.distributed import hints
    d_in, h, dk, dv = mlstm_dims(cfg)
    b, t, _ = x.shape
    up = x @ p["up_proj"]
    z = x @ p["z_proj"]
    c, tail = _causal_conv(up, p["conv"], conv_tail)
    c = jax.nn.silu(c)
    # 'mlstm_qk' hint (no-op without a rule): with wq/wk TP-sharded on
    # their output dim, the per-chunk score einsums contract over a
    # sharded dk -> an all-reduce per chunk per layer.  Pinning q/k
    # replicated HERE gathers once per layer instead (33 MB vs 16 ARs).
    q = hints.constrain(
        (c @ p["wq"]).reshape(b, t, h, dk), "mlstm_qk").astype(jnp.float32)
    q = q / math.sqrt(dk)
    k = hints.constrain(
        (c @ p["wk"]).reshape(b, t, h, dk), "mlstm_qk").astype(jnp.float32)
    v = up.reshape(b, t, h, dv).astype(jnp.float32)
    ig = (c @ p["wi_gate"]).astype(jnp.float32) + p["b_i"]
    fg = (c @ p["wf_gate"]).astype(jnp.float32) + p["b_f"]
    return up, z, q, k, v, ig, fg, tail


def _head_norm_gate(p, hmat, z, x_dtype):
    """Per-head RMS norm, scale, silu(z) gate."""
    ms = jnp.mean(jnp.square(hmat), axis=-1, keepdims=True)
    hn = hmat * lax.rsqrt(ms + 1e-6)
    b, t = hmat.shape[:2]
    hn = hn.reshape(b, t, -1) * p["head_norm"]
    return (hn * jax.nn.silu(z.astype(jnp.float32))).astype(x_dtype)


def mlstm_apply(cfg: ModelConfig, p: dict, x: jax.Array,
                state: dict | None = None, return_state: bool = False):
    b = x.shape[0]
    st = state or init_mlstm_state(cfg, b)
    up, z, q, k, v, ig, fg, tail = _mlstm_project(cfg, p, x, st["conv"])
    h, (c_new, n_new, m_new) = _mlstm_chunkwise_parallel(
        q, k, v, ig, fg, cfg.xlstm.chunk, (st["C"], st["n"], st["m"]))
    y = _head_norm_gate(p, h, z, x.dtype) @ p["down_proj"]
    if not return_state:
        return y, None
    return y, {"C": c_new, "n": n_new, "m": m_new, "conv": tail}


def mlstm_decode(cfg: ModelConfig, p: dict, x: jax.Array, state: dict):
    """Single-token recurrence.  x (B,1,d)."""
    up, z, q, k, v, ig, fg, tail = _mlstm_project(cfg, p, x, state["conv"])
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]             # (B,H,dk/dv)
    i1, f1 = ig[:, 0], fg[:, 0]                        # (B,H)
    logf = jax.nn.log_sigmoid(f1)
    m_new = jnp.maximum(logf + state["m"], i1)
    coef_f = jnp.exp(logf + state["m"] - m_new)
    coef_i = jnp.exp(i1 - m_new)
    c_new = coef_f[..., None, None] * state["C"] + coef_i[..., None, None] \
        * (k1[..., :, None] * v1[..., None, :])
    n_new = coef_f[..., None] * state["n"] + coef_i[..., None] * k1
    num = _einsum("bhk,bhkd->bhd", q1, c_new)
    den = _einsum("bhk,bhk->bh", q1, n_new)
    floor = jnp.exp(jnp.minimum(-m_new, _M_CLAMP))
    h = (num / jnp.maximum(jnp.abs(den), floor)[..., None])[:, None]
    y = _head_norm_gate(p, h, z, x.dtype) @ p["down_proj"]
    return y, {"C": c_new, "n": n_new, "m": m_new, "conv": tail}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def init_slstm(cfg: ModelConfig, key: jax.Array) -> dict:
    x = cfg.xlstm
    dt = dtype_of(cfg)
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    ff = int(d * x.slstm_ff_factor)
    ks = jax.random.split(key, 12)
    p = {}
    for n, kk in zip(("z", "i", "f", "o"), ks[:4]):
        p[f"w_{n}"] = common.dense_init(kk, (d, d), dt)
    for n, kk in zip(("z", "i", "f", "o"), ks[4:8]):
        p[f"r_{n}"] = common.dense_init(kk, (h, dh, dh), dt, fan_in=dh)
    p["b_z"] = jnp.zeros((d,), jnp.float32)
    p["b_i"] = jnp.zeros((d,), jnp.float32)
    p["b_f"] = jnp.full((d,), 3.0, jnp.float32)
    p["b_o"] = jnp.zeros((d,), jnp.float32)
    p["head_norm"] = jnp.ones((d,), jnp.float32)
    p["ff_gate"] = common.dense_init(ks[8], (d, ff), dt)
    p["ff_up"] = common.dense_init(ks[9], (d, ff), dt)
    p["ff_down"] = common.dense_init(ks[10], (ff, d), dt, fan_in=ff)
    return p


def init_slstm_state(cfg: ModelConfig, batch: int) -> dict:
    d = cfg.d_model
    return {
        "h": jnp.zeros((batch, d), jnp.float32),
        "c": jnp.zeros((batch, d), jnp.float32),
        "n": jnp.full((batch, d), 1e-6, jnp.float32),
        "m": jnp.zeros((batch, d), jnp.float32),
    }


def _slstm_cell(cfg, p, xz, xi, xf, xo, state):
    """One recurrent step.  x* (B,d) f32 pre-activations from the input side;
    state dict of (B,d) f32.  Returns (h, new_state)."""
    h_heads = state["h"].reshape(-1, cfg.n_heads,
                                 cfg.d_model // cfg.n_heads)

    def rec(w):
        return _einsum("bhd,hde->bhe", h_heads,
                          w.astype(jnp.float32)).reshape(state["h"].shape)

    z = jnp.tanh(xz + rec(p["r_z"]) + p["b_z"])
    i_pre = xi + rec(p["r_i"]) + p["b_i"]
    f_pre = xf + rec(p["r_f"]) + p["b_f"]
    o = jax.nn.sigmoid(xo + rec(p["r_o"]) + p["b_o"])
    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + state["m"], i_pre)
    coef_f = jnp.exp(logf + state["m"] - m_new)
    coef_i = jnp.exp(i_pre - m_new)
    c_new = coef_f * state["c"] + coef_i * z
    n_new = coef_f * state["n"] + coef_i
    h_new = o * c_new / jnp.maximum(n_new, 1e-6)
    return h_new, {"h": h_new, "c": c_new, "n": n_new, "m": m_new}


def _slstm_ff(cfg, p, h, x_dtype):
    ms = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
    hn = (h * lax.rsqrt(ms + 1e-6) * p["head_norm"]).astype(x_dtype)
    f = jax.nn.gelu(hn @ p["ff_gate"], approximate=True) * (hn @ p["ff_up"])
    return f @ p["ff_down"]


def slstm_apply(cfg: ModelConfig, p: dict, x: jax.Array,
                state: dict | None = None, return_state: bool = False):
    """Strictly-sequential scan over T.  x (B,T,d)."""
    b, t, d = x.shape
    st = state or init_slstm_state(cfg, b)
    xz = (x @ p["w_z"]).astype(jnp.float32)
    xi = (x @ p["w_i"]).astype(jnp.float32)
    xf = (x @ p["w_f"]).astype(jnp.float32)
    xo = (x @ p["w_o"]).astype(jnp.float32)

    def body(carry, inp):
        h, new = _slstm_cell(cfg, p, *inp, carry)
        return new, h

    xs = tuple(jnp.swapaxes(a, 0, 1) for a in (xz, xi, xf, xo))
    st_new, hs = lax.scan(body, st, xs)
    h_seq = jnp.swapaxes(hs, 0, 1)                     # (B,T,d) f32
    y = _slstm_ff(cfg, p, h_seq, x.dtype)
    return y, (st_new if return_state else None)


def slstm_decode(cfg: ModelConfig, p: dict, x: jax.Array, state: dict):
    xz = (x[:, 0] @ p["w_z"]).astype(jnp.float32)
    xi = (x[:, 0] @ p["w_i"]).astype(jnp.float32)
    xf = (x[:, 0] @ p["w_f"]).astype(jnp.float32)
    xo = (x[:, 0] @ p["w_o"]).astype(jnp.float32)
    h, st_new = _slstm_cell(cfg, p, xz, xi, xf, xo, state)
    y = _slstm_ff(cfg, p, h[:, None], x.dtype)
    return y, st_new
