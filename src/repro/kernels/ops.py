"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * shape padding to MXU-aligned blocks (and un-padding),
  * jax PRNG key → kernel seed derivation,
  * straight-through / QAT gradients via custom_vjp,
  * backend dispatch, two layers deep:
      - the PUBLIC entry points (crossbar_mac, wta_counts, stoch_round*,
        paged_attention*) route through the active device backend
        (`repro.kernels.backend` — Sim by default, the seam for
        hardware-in-the-loop later);
      - the Sim implementations (`*_sim` below) then pick compiled
        Pallas on TPU (:func:`tpu_kernels`).  Off TPU the serving kernels
        (paged attention, chunked prefill, serving stochastic rounding)
        run their pure-jnp oracles; the analog kernels (crossbar MAC, WTA
        counts, stochastic rounding for training) run in the Pallas TPU
        interpreter.
  * under a serving mesh, ``shard_map`` around every serving kernel
    (:func:`shard_over_kv_heads`): GSPMD cannot partition a Mosaic call.

All wrappers accept arbitrary leading batch dims on ``x``.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro import parallel

from . import backend as _backend
from . import crossbar_mac as _cb
from . import prng, ref
from . import stoch_round as _sr
from . import wta_kernel as _wta
from repro.core.physics import BOLTZMANN_K, PROBIT_SCALE


def _interpret_mode():
    """``pallas_call(interpret=...)`` for the analog kernels: compiled on
    TPU, the Pallas TPU interpreter on the CPU backend (tests and CPU
    training runs of the analog modes)."""
    if tpu_kernels():
        return False
    return pltpu.InterpretParams()


def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _seed_from_key(key: jax.Array) -> jax.Array:
    s = prng.key_to_seed(key)
    return jax.lax.bitcast_convert_type(s, jnp.int32).reshape((1,))


def _qstep(dp) -> float:
    return (dp.w_max - dp.w_min) / max(dp.n_levels - 1, 1)


def _noise_params(dp, k_rows: int) -> tuple:
    return (
        4.0 * BOLTZMANN_K * dp.temperature * dp.delta_f,
        dp.g0,
        dp.g_ref,
        dp.v_read,
        float(k_rows),
    )


# ---------------------------------------------------------------------------
# crossbar_mac: fused analog matmul (+ optional stochastic binarization).
# ---------------------------------------------------------------------------


def _range_scale(w):
    """Per-layer dynamic-range scale s = max|W| (see core.analog)."""
    return jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(w)), 1e-6))


def _crossbar_fwd_impl(x2d, w, seed_arr, cfg, binarize, bm, bn, bk, interp):
    m, k = x2d.shape
    n = w.shape[1]
    dp = cfg.device
    if cfg.calibrated:
        # dynamic-range mapping: devices hold w/s; the comparator slope (via
        # per-layer V_r) absorbs s: sigma_norm = 1.702 / (beta·s) realizes
        # P = sigmoid(beta·s·z_norm) = sigmoid(beta·z).
        s = _range_scale(w)
        w_in = w / s
        if binarize:
            sigma = jnp.float32(PROBIT_SCALE) / (cfg.beta * s)
        else:
            sigma = jnp.float32(cfg.linear_sigma)  # high-SNR linear read
    else:
        s = jnp.float32(1.0)
        w_in = w
        sigma = jnp.float32(PROBIT_SCALE / cfg.beta)  # unused (physical)
    params = jnp.concatenate(
        [seed_arr, jax.lax.bitcast_convert_type(sigma, jnp.int32).reshape(1)]
    )
    xp = _pad_to(_pad_to(x2d, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w_in, bk, 0), bn, 1)
    out = _cb.crossbar_mac_pallas(
        xp,
        wp,
        params,
        binarize=binarize,
        physical_noise=not cfg.calibrated,
        noise_params=_noise_params(dp, k),
        quantize=cfg.quantize,
        qstep=_qstep(dp),
        w_min=dp.w_min,
        w_max=dp.w_max,
        bm=bm,
        bn=bn,
        bk=bk,
        valid_k=k,
        interpret=interp,
    )
    out = out[:m, :n]
    if not binarize and cfg.calibrated:
        out = out * s  # scale normalized linear readout back
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _crossbar_mac_core(x2d, w, seed_arr, cfg, binarize):
    interp = _interpret_mode()
    return _crossbar_fwd_impl(
        x2d, w, seed_arr, cfg, binarize, _cb.DEF_BM, _cb.DEF_BN, _cb.DEF_BK,
        interp,
    )


def _crossbar_fwd(x2d, w, seed_arr, cfg, binarize):
    y = _crossbar_mac_core(x2d, w, seed_arr, cfg, binarize)
    return y, (x2d, w)


def _crossbar_bwd(cfg, binarize, res, g):
    """QAT/STE backward.

    binarize=True : y ~ Bern(sigmoid(beta z)); STE surrogate E[y] gives
                    dz = g · beta · p(1-p)   with p recomputed (remat).
    binarize=False: y = z + noise (noise treated as additive const) => dz = g.
    Quantizer: straight-through.  With dynamic-range normalization the clip
    never saturates (w/s ∈ [-1,1]); the physical path keeps the clip mask.
    """
    x2d, w = res
    dp = cfg.device
    wq = w
    if cfg.quantize:
        step = _qstep(dp)
        if cfg.calibrated:
            s = _range_scale(w)
            wn = jnp.clip(w / s, dp.w_min, dp.w_max)
            wq = s * (
                jnp.round((wn - dp.w_min) * jnp.float32(1.0 / step)) * step
                + dp.w_min
            )
        else:
            wq = jnp.clip(w, dp.w_min, dp.w_max)
            wq = (
                jnp.round((wq - dp.w_min) * jnp.float32(1.0 / step)) * step
                + dp.w_min
            )
    if binarize:
        z = x2d @ wq
        p = jax.nn.sigmoid(cfg.beta * z)
        dz = g * cfg.beta * p * (1.0 - p)
    else:
        dz = g
    dx = dz @ wq.T
    dw = x2d.T @ dz
    if cfg.quantize and not cfg.calibrated:
        dw = dw * ((w >= dp.w_min) & (w <= dp.w_max)).astype(dw.dtype)
    return dx, dw, None


_crossbar_mac_core.defvjp(_crossbar_fwd, _crossbar_bwd)


def crossbar_mac(
    x: jax.Array,
    w: jax.Array,
    key: jax.Array,
    cfg: Any,
    binarize: bool = True,
) -> jax.Array:
    """Fused RACA matmul.  x: (..., K) f32, w: (K, N) f32 → (..., N) f32.

    Dispatches through the active device backend (Sim routes to
    :func:`crossbar_mac_sim`, i.e. today's Pallas/interpret math)."""
    return _backend.get_backend().crossbar_mac(x, w, key, cfg, binarize)


def crossbar_mac_sim(
    x: jax.Array,
    w: jax.Array,
    key: jax.Array,
    cfg: Any,
    binarize: bool = True,
) -> jax.Array:
    """Sim-backend implementation (the pre-seam wrapper, bit-identical)."""
    lead = x.shape[:-1]
    x2d = x.reshape((-1, x.shape[-1])).astype(jnp.float32)
    y = _crossbar_mac_core(
        x2d, w.astype(jnp.float32), _seed_from_key(key), cfg, binarize
    )
    return y.reshape(lead + (w.shape[1],))


def crossbar_mac_reference(
    x: jax.Array, w: jax.Array, key: jax.Array, cfg: Any, binarize: bool = True
) -> jax.Array:
    """Same padding/seed/normalization pipeline, oracle math — for
    kernel-vs-ref tests."""
    lead = x.shape[:-1]
    x2d = x.reshape((-1, x.shape[-1])).astype(jnp.float32)
    m, k = x2d.shape
    n = w.shape[1]
    wf = w.astype(jnp.float32)
    dp = cfg.device
    if cfg.calibrated:
        s = _range_scale(wf)
        w_in = wf / s
        if binarize:
            sigma = jnp.float32(PROBIT_SCALE) / (cfg.beta * s)
        else:
            sigma = jnp.float32(cfg.linear_sigma)
    else:
        s = jnp.float32(1.0)
        w_in = wf
        sigma = jnp.float32(PROBIT_SCALE / cfg.beta)
    xp = _pad_to(_pad_to(x2d, _cb.DEF_BM, 0), _cb.DEF_BK, 1)
    wp = _pad_to(_pad_to(w_in, _cb.DEF_BK, 0), _cb.DEF_BN, 1)
    out = ref.crossbar_mac_ref(
        xp,
        wp,
        prng.key_to_seed(key),
        binarize=binarize,
        physical_noise=not cfg.calibrated,
        sigma_z=sigma,
        noise_params=_noise_params(dp, k),
        quantize=cfg.quantize,
        qstep=_qstep(dp),
        w_min=dp.w_min,
        w_max=dp.w_max,
        valid_k=k,
    )
    out = out[:m, :n]
    if not binarize and cfg.calibrated:
        out = out * s
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# Canary probe: fixed known-answer crossbar MAC for drift detection.
# ---------------------------------------------------------------------------

_CANARY_ROWS, _CANARY_COLS = 128, 8


@functools.lru_cache(maxsize=1)
def _canary_operands():
    import numpy as np

    rng = np.random.default_rng(0xCA9A31)
    x = rng.uniform(-1.0, 1.0, (1, _CANARY_ROWS)).astype(np.float32)
    w = rng.uniform(-1.0, 1.0, (_CANARY_ROWS, _CANARY_COLS)).astype(
        np.float32
    )
    return x, w


@functools.lru_cache(maxsize=1)
def _canary_cfg():
    from repro.core.analog import AnalogConfig

    # Unquantized calibrated linear read: the healthy answer is exactly
    # x @ w plus a small zero-mean read noise, so drift/stuck-at shifts
    # are separable from the noise floor by a relative-error threshold.
    return AnalogConfig(
        mode="analog_linear", quantize=False, calibrated=True,
        linear_sigma=0.01,
    )


def canary_expected():
    """Host-side known answer of the canary MAC (float32 ndarray)."""
    x, w = _canary_operands()
    return x @ w


def canary_mac(key: jax.Array) -> jax.Array:
    """Fire the canary: a fixed (1, 128) x (128, 8) linear crossbar read
    through the ACTIVE device backend.

    On a healthy backend the result is ``canary_expected()`` plus
    ~linear_sigma read noise; conductance drift scales it multiplicatively
    and stuck-at cells shift it, so a relative-error check against the
    known answer detects substrate degradation without touching live
    traffic.  Traced + jitted by the serving engine, and rebuilt alongside
    the other entry points when the backend's fault_version bumps."""
    x, w = _canary_operands()
    return crossbar_mac(
        jnp.asarray(x), jnp.asarray(w), key, _canary_cfg(), binarize=False
    )


# ---------------------------------------------------------------------------
# WTA vote counting.
# ---------------------------------------------------------------------------


def wta_counts(
    z: jax.Array,
    key: jax.Array,
    *,
    n_trials: int,
    vth0: float,
    sigma_z: float,
) -> jax.Array:
    """Winner counts over T WTA trials.  z: (..., C) → counts (..., C).

    Inference-path readout: gradients are stopped (the training surrogate is
    softmax cross-entropy on the pre-activations, as in the paper).
    Dispatches through the active device backend."""
    return _backend.get_backend().wta_counts(
        z, key, n_trials=n_trials, vth0=vth0, sigma_z=sigma_z
    )


def wta_counts_sim(
    z: jax.Array,
    key: jax.Array,
    *,
    n_trials: int,
    vth0: float,
    sigma_z: float,
) -> jax.Array:
    """Sim-backend implementation (the pre-seam wrapper, bit-identical)."""
    lead = z.shape[:-1]
    c = z.shape[-1]
    z2d = z.reshape((-1, c)).astype(jnp.float32)
    bm = _wta.DEF_BM
    zp = _pad_to(_pad_to(z2d, bm, 0), 128, 1)
    out = _wta.wta_counts_pallas(
        jax.lax.stop_gradient(zp),
        _seed_from_key(key),
        n_trials=n_trials,
        vth0=vth0,
        sigma_z=sigma_z,
        valid_c=c,
        bm=bm,
        interpret=_interpret_mode(),
    )
    return out[: z2d.shape[0], :c].reshape(lead + (c,))


def wta_counts_reference(
    z: jax.Array, key: jax.Array, *, n_trials: int, vth0: float, sigma_z: float
) -> jax.Array:
    lead = z.shape[:-1]
    c = z.shape[-1]
    z2d = z.reshape((-1, c)).astype(jnp.float32)
    bm = _wta.DEF_BM
    zp = _pad_to(_pad_to(z2d, bm, 0), 128, 1)
    out = ref.wta_counts_ref(
        zp,
        prng.key_to_seed(key),
        n_trials=n_trials,
        vth0=vth0,
        sigma_z=sigma_z,
        valid_c=c,
        bm=bm,
    )
    return out[: z2d.shape[0], :c].reshape(lead + (c,))


# ---------------------------------------------------------------------------
# Paged attention (serving decode path).
# ---------------------------------------------------------------------------


def tpu_kernels() -> bool:
    """Whether the serving kernels (paged decode, chunked prefill, int8
    stochastic rounding) run as compiled Pallas.  They do on TPU; on
    every other backend their pure-jnp oracles stand in.  The one switch
    the serving path asks (tests steer it by patching this function)."""
    return jax.default_backend() == "tpu"


def shard_over_kv_heads(kernel, mesh):
    """Run a paged attention kernel once per chip of ``mesh``'s model axis.

    ``kernel(q, k_pages, v_pages, table, idx, *, k_scale, v_scale, **kw)``
    is :func:`paged_attention_pallas` or
    :func:`paged_prefill_attention_pallas`.  Each chip attends with its
    own ``Hkv / |model|`` KV heads' pages (the pool's sharding: kv_heads
    over "model") and the matching query heads, then the readouts are
    all-gathered over heads.  So every chip leaves with the whole (…, H,
    Dh) readout, and no later contraction is split across chips: the
    output projection runs as on one chip.  Where the model axis does not
    divide Hkv the pool is replicated and each chip runs every head.
    Table and position arguments are replicated scalars."""
    from jax.sharding import PartitionSpec as P

    m = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    def run(q, k_pages, v_pages, table, idx, *, k_scale=None,
            v_scale=None, **kw):
        ax = "model" if k_pages.shape[2] % m == 0 else None
        heads = P(None, ax, None)            # q / readout: (B|S, H, Dh)
        pages = P(None, None, ax, None)      # (P, bs, Hkv, Dh)
        scales = P(None, None, ax)           # (P, bs, Hkv)
        args = [q, k_pages, v_pages, table, idx]
        specs = [heads, pages, pages, P(), P()]
        if k_scale is not None:
            args += [k_scale, v_scale]
            specs += [scales, scales]

        def body(q, kp, vp, t, i, *sc):
            out = kernel(
                q, kp, vp, t, i,
                k_scale=sc[0] if sc else None,
                v_scale=sc[1] if sc else None,
                **kw,
            )
            if ax is None:
                return out
            return jax.lax.all_gather(out, "model", axis=1, tiled=True)

        return jax.shard_map(
            body, mesh=mesh, in_specs=tuple(specs), out_specs=P(),
            check_vma=False,
        )(*args)

    return run


def _on_kernel_mesh(kernel):
    """``kernel``, run per chip over the KV heads when a kernel mesh is
    installed (:func:`repro.parallel.kernel_mesh`)."""
    mesh = parallel.current_kernel_mesh()
    return kernel if mesh is None else shard_over_kv_heads(kernel, mesh)


def paged_attention(
    q: jax.Array,        # (B, H, Dh)
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) — cache dtype or int8 codes
    v_pages: jax.Array,
    table: jax.Array,    # (B, W) int32
    pos: jax.Array,      # (B,) int32
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Block-table decode attention, dispatched through the active device
    backend (Sim routes to :func:`paged_attention_sim`)."""
    return _backend.get_backend().paged_attention(
        q, k_pages, v_pages, table, pos,
        kind=kind, local_window=local_window, softcap=softcap,
        k_scale=k_scale, v_scale=v_scale,
    )


def paged_attention_sim(
    q: jax.Array,        # (B, H, Dh)
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) — cache dtype or int8 codes
    v_pages: jax.Array,
    table: jax.Array,    # (B, W) int32
    pos: jax.Array,      # (B,) int32
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Block-table decode attention: compiled Pallas kernel on TPU, the
    pure-jnp oracle elsewhere.

    With int8 ``k_pages``/``v_pages`` the per-(page, slot-in-page, head)
    ``k_scale``/``v_scale`` planes ride along and dequantization is fused
    into the score/value math — int8 blocks are what crosses HBM; no
    dequantized cache is ever materialized.

    Unlike the crossbar kernels, the off-TPU fallback is the oracle rather
    than interpret-mode emulation: this sits in the serving engine's
    per-token hot loop, where interpret mode would bury the very latency
    the paged layout removes.  Kernel-vs-oracle agreement is pinned by
    tests/test_kernels.py (interpret mode on small shapes)."""
    from . import paged_attention as _pa

    if not tpu_kernels():
        return ref.paged_attention_ref(
            q, k_pages, v_pages, table, pos,
            kind=kind, local_window=local_window, softcap=softcap,
            k_scale=k_scale, v_scale=v_scale,
        )
    return _on_kernel_mesh(_pa.paged_attention_pallas)(
        q, k_pages, v_pages, table, pos,
        kind=kind, local_window=local_window, softcap=softcap,
        k_scale=k_scale, v_scale=v_scale,
        interpret=False,
    )


def paged_prefill_attention(
    q: jax.Array,        # (S, H, Dh) — one request's suffix-chunk queries
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) — cache dtype or int8 codes
    v_pages: jax.Array,
    table: jax.Array,    # (W,) int32 — the request's block-table row
    q0: jax.Array,       # () int32 absolute position of the first query
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Prefix-aware chunked-prefill attention, dispatched through the
    active device backend (Sim routes to
    :func:`paged_prefill_attention_sim`)."""
    return _backend.get_backend().paged_prefill_attention(
        q, k_pages, v_pages, table, q0,
        kind=kind, local_window=local_window, softcap=softcap,
        k_scale=k_scale, v_scale=v_scale,
    )


def paged_prefill_attention_sim(
    q: jax.Array,        # (S, H, Dh) — one request's suffix-chunk queries
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) — cache dtype or int8 codes
    v_pages: jax.Array,
    table: jax.Array,    # (W,) int32 — the request's block-table row
    q0: jax.Array,       # () int32 absolute position of the first query
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Prefix-aware chunked-prefill attention: the suffix chunk's queries
    attend into every block of the request's table — shared prefix pages
    included — at their absolute positions.  Compiled Pallas kernel on
    TPU, the pure-jnp oracle elsewhere (interpret mode would bury the
    prefill latency the suffix path removes; kernel-vs-oracle agreement is
    pinned by tests/test_kernels.py).  int8 pools fuse dequant into the
    score/value math exactly like :func:`paged_attention`.

    NOTE: the serving engine's off-TPU bf16 path does NOT come through
    here — it uses the gather + attend_full route in models/attention.py,
    whose numerics are bit-identical to the dense monolithic prefill (the
    dense-vs-paged equivalence oracle).  This dispatch serves the TPU hot
    path and the int8 fused-dequant math on every backend."""
    from . import prefill_attention as _pf

    if not tpu_kernels():
        return ref.prefill_attention_ref(
            q, k_pages, v_pages, table, q0,
            kind=kind, local_window=local_window, softcap=softcap,
            k_scale=k_scale, v_scale=v_scale,
        )
    return _on_kernel_mesh(_pf.paged_prefill_attention_pallas)(
        q, k_pages, v_pages, table, q0,
        kind=kind, local_window=local_window, softcap=softcap,
        k_scale=k_scale, v_scale=v_scale,
        interpret=False,
    )


# ---------------------------------------------------------------------------
# Stochastic rounding.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _stoch_round_core(x2d, seed_arr, step, lo, hi):
    xp = _pad_to(_pad_to(x2d, _sr.DEF_BM, 0), _sr.DEF_BN, 1)
    out = _sr.stoch_round_pallas(
        xp, seed_arr, step=step, lo=lo, hi=hi, interpret=_interpret_mode()
    )
    return out[: x2d.shape[0], : x2d.shape[1]]


def _sr_fwd(x2d, seed_arr, step, lo, hi):
    return _stoch_round_core(x2d, seed_arr, step, lo, hi), x2d


def _sr_bwd(step, lo, hi, x2d, g):
    mask = ((x2d >= lo) & (x2d <= hi)).astype(g.dtype)
    return g * mask, None


_stoch_round_core.defvjp(_sr_fwd, _sr_bwd)


def stoch_round(
    x: jax.Array, key: jax.Array, *, step: float, lo: float, hi: float
) -> jax.Array:
    """Unbiased stochastic rounding onto {lo + k·step}; STE gradient.
    Dispatches through the active device backend."""
    return _backend.get_backend().stoch_round(x, key, step=step, lo=lo, hi=hi)


def stoch_round_sim(
    x: jax.Array, key: jax.Array, *, step: float, lo: float, hi: float
) -> jax.Array:
    """Sim-backend implementation (the pre-seam wrapper, bit-identical)."""
    shape = x.shape
    x2d = x.reshape((-1, shape[-1])).astype(jnp.float32)
    y = _stoch_round_core(x2d, _seed_from_key(key), step, lo, hi)
    return y.reshape(shape)


def stoch_round_reference(
    x: jax.Array, key: jax.Array, *, step: float, lo: float, hi: float
) -> jax.Array:
    shape = x.shape
    x2d = x.reshape((-1, shape[-1])).astype(jnp.float32)
    xp = _pad_to(_pad_to(x2d, _sr.DEF_BM, 0), _sr.DEF_BN, 1)
    out = ref.stoch_round_ref(
        xp, prng.key_to_seed(key), step=step, lo=lo, hi=hi
    )
    return out[: x2d.shape[0], : x2d.shape[1]].reshape(shape)


def stoch_round_serving(
    x: jax.Array, seed: jax.Array, *, step: float, lo: float, hi: float
) -> jax.Array:
    """Serving-hot-path stochastic rounding, dispatched through the active
    device backend (Sim routes to :func:`stoch_round_serving_sim`)."""
    return _backend.get_backend().stoch_round_serving(
        x, seed, step=step, lo=lo, hi=hi
    )


def stoch_round_serving_sim(
    x: jax.Array, seed: jax.Array, *, step: float, lo: float, hi: float
) -> jax.Array:
    """Stochastic rounding for the serving hot path, seeded by a raw
    uint32 counter-PRNG seed (traced scalar) instead of a jax PRNG key.

    Backend dispatch mirrors :func:`paged_attention`: the compiled Pallas
    kernel on TPU, the pure-jnp oracle elsewhere — interpret-mode emulation
    would bury the per-token decode latency this feeds.  Kernel and oracle
    share the counter PRNG, so the rounding decisions are bit-identical
    across backends for a given (seed, element) pair."""
    shape = x.shape
    x2d = x.reshape((-1, shape[-1])).astype(jnp.float32)
    xp = _pad_to(_pad_to(x2d, _sr.DEF_BM, 0), _sr.DEF_BN, 1)
    seed_u = jnp.asarray(seed).astype(jnp.uint32)
    if not tpu_kernels():
        out = ref.stoch_round_ref(xp, seed_u, step=step, lo=lo, hi=hi)
    else:
        seed_arr = jax.lax.bitcast_convert_type(seed_u, jnp.int32).reshape(1)
        kernel = functools.partial(
            _sr.stoch_round_pallas, step=step, lo=lo, hi=hi, interpret=False
        )
        mesh = parallel.current_kernel_mesh()
        if mesh is not None:
            # GSPMD cannot partition a Mosaic call: every chip rounds the
            # whole (small) write, with the same counters and bits
            from jax.sharding import PartitionSpec as P

            kernel = jax.shard_map(
                kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False,
            )
        out = kernel(xp, seed_arr)
    return out[: x2d.shape[0], : x2d.shape[1]].reshape(shape)


def quantize_kv_int8(
    x: jax.Array, seed: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-row int8 quantization with unbiased stochastic rounding.

    ``x`` is (..., Dh); returns (codes int8 (..., Dh), scale f32 (...,)).
    The scale is the row's max |value| so codes span the full [-127, 127]
    grid, and each element is stochastically rounded to an adjacent integer
    level (``E[codes] = x / scale * 127``) — the paper's conductance-
    programming primitive (§II-B, kernels/stoch_round) applied to the KV
    cache, so quantized cache writes stay unbiased exactly like programming
    weights onto discrete device levels.  Dequantization is never
    materialized: attention multiplies *scores* by ``scale / 127`` (see
    paged_attention / models.attention.attend_one_token)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-6)
    t = xf / scale[..., None] * 127.0
    q = stoch_round_serving(t, seed, step=1.0, lo=-127.0, hi=127.0)
    return q.astype(jnp.int8), scale


def quantize_kv_pair_int8(
    k: jax.Array, v: jax.Array, seed: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Quantize a K/V pair from ONE seed with decorrelated rounding streams.

    The v stream is offset by the golden-ratio constant so k and v never
    share per-element rounding draws (identical draws would correlate
    their quantization errors and bias attention readouts).  Both int8
    cache-write paths (prefill insert in launch/specs.py, decode write in
    models/attention.py) go through here so the offset cannot drift.

    Returns (k_codes, k_scale, v_codes, v_scale)."""
    seed_u = jnp.asarray(seed).astype(jnp.uint32)
    k8, ks = quantize_kv_int8(k, seed_u)
    v8, vs = quantize_kv_int8(v, seed_u + jnp.uint32(0x9E3779B9))
    return k8, ks, v8, vs
