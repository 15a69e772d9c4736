"""Per-kernel validation: Pallas (TPU-interpret) vs pure-jnp oracles.

The kernels share a counter-based PRNG with the oracles, so stochastic
paths are compared bit-exactly (binary agreement / identical levels), and
deterministic paths with f32-matmul tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core.analog import AnalogConfig
from repro.core.physics import DeviceParams, calibrate_v_read
from repro.kernels import ops, prng

CFG = AnalogConfig(
    mode="analog_stochastic", device=calibrate_v_read(DeviceParams(), 512)
)
KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# crossbar_mac
# ---------------------------------------------------------------------------

SHAPES = [
    (8, 64, 16),       # tiny, all dims sub-block
    (100, 300, 200),   # unaligned
    (128, 512, 128),   # exactly one block
    (64, 1200, 130),   # multi-K-block accumulation
    (257, 513, 129),   # off-by-one on every dim
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_crossbar_linear_matches_oracle(m, k, n, dtype):
    x = jax.random.normal(KEY, (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) * 0.05
    y_k = ops.crossbar_mac(x, w, KEY, CFG, binarize=False)
    y_r = ops.crossbar_mac_reference(x, w, KEY, CFG, binarize=False)
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_r), atol=2e-5, rtol=1e-5
    )


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_crossbar_binary_agreement(m, k, n):
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.05
    y_k = ops.crossbar_mac(x, w, KEY, CFG, binarize=True)
    y_r = ops.crossbar_mac_reference(x, w, KEY, CFG, binarize=True)
    assert set(np.unique(np.asarray(y_k))) <= {0.0, 1.0}
    # identical PRNG; only f32 matmul reassociation at threshold can differ
    agreement = float((y_k == y_r).mean())
    assert agreement > 0.9995, agreement


def test_crossbar_physical_noise_path():
    cfgp = AnalogConfig(
        mode="analog_stochastic", device=CFG.device, calibrated=False
    )
    x = jax.random.normal(KEY, (64, 512))
    w = jax.random.normal(jax.random.PRNGKey(2), (512, 128)) * 0.05
    y_k = ops.crossbar_mac(x, w, KEY, cfgp, binarize=True)
    y_r = ops.crossbar_mac_reference(x, w, KEY, cfgp, binarize=True)
    assert float((y_k == y_r).mean()) > 0.9995


def test_crossbar_batched_leading_dims():
    x = jax.random.normal(KEY, (4, 6, 96))
    w = jax.random.normal(jax.random.PRNGKey(3), (96, 32)) * 0.1
    y = ops.crossbar_mac(x, w, KEY, CFG, binarize=False)
    assert y.shape == (4, 6, 32)


def test_crossbar_gradients_match_ste_surrogate():
    """Backward of the kernel == analytic STE formula."""
    x = jax.random.normal(KEY, (32, 128))
    w = jax.random.normal(jax.random.PRNGKey(4), (128, 64)) * 0.1

    g_w = jax.grad(
        lambda w: jnp.sum(ops.crossbar_mac(x, w, KEY, CFG, True) ** 2)
    )(w)
    assert bool(jnp.all(jnp.isfinite(g_w)))
    # compare direction with the dense surrogate E[y]=sigmoid(z)
    from repro.core import analog as A

    wq = A.quantize_normalized(w, CFG)
    y_hard = ops.crossbar_mac(x, w, KEY, CFG, True)

    def surrogate(w2):
        # identity-STE through the quantizer (jnp.round has zero grad)
        wq2 = w2 + jax.lax.stop_gradient(wq - w2)
        p = jax.nn.sigmoid(x @ wq2)
        return jnp.sum(
            y_hard**2 + 2 * y_hard * (p - jax.lax.stop_gradient(p))
        )

    # d/dw of sum(y^2) under STE: 2·y·dp/dw
    g_ref = jax.grad(surrogate)(w)
    np.testing.assert_allclose(
        np.asarray(g_w), np.asarray(g_ref), atol=3e-5, rtol=1e-3
    )


def test_noise_statistics_linear_mode():
    """Linear (high-SNR) readout: residual noise std == s·linear_sigma."""
    x = jnp.zeros((256, 512))
    w = jax.random.normal(jax.random.PRNGKey(9), (512, 256)) * 0.05
    cfg_nq = AnalogConfig(
        mode="analog_stochastic", device=CFG.device, quantize=False
    )
    y = ops.crossbar_mac(x, w, KEY, cfg_nq, binarize=False)
    # x = 0 => output is pure noise: std = s·linear_sigma
    s_expect = float(jnp.max(jnp.abs(w))) * cfg_nq.linear_sigma
    assert abs(float(jnp.std(y)) - s_expect) / s_expect < 0.05
    assert abs(float(jnp.mean(y))) < s_expect * 0.05


def test_fire_rate_half_at_zero_drive():
    """Comparator at z=0 fires with probability 1/2 (calibration anchor)."""
    x = jnp.zeros((128, 256))
    w = jax.random.normal(jax.random.PRNGKey(10), (256, 128)) * 0.05
    y = ops.crossbar_mac(x, w, KEY, CFG, binarize=True)
    assert abs(float(y.mean()) - 0.5) < 0.02


# ---------------------------------------------------------------------------
# wta kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,c", [(1, 10), (7, 10), (130, 5), (16, 200)])
def test_wta_kernel_bit_exact(b, c):
    z = jax.random.normal(jax.random.PRNGKey(5), (b, c))
    kw = dict(n_trials=64, vth0=2.897, sigma_z=1.702)
    ck = ops.wta_counts(z, KEY, **kw)
    cr = ops.wta_counts_reference(z, KEY, **kw)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


def test_wta_kernel_matches_core_distribution():
    """Kernel votes converge to the same softmax the core simulator gives."""
    from repro.core import wta as W

    z = jnp.asarray([[1.0, 0.0, -1.0, 0.5, 2.0, -0.5, 0.2, -1.5]])
    theta = W.calibrated_threshold()
    counts = ops.wta_counts(z, KEY, n_trials=20_000, vth0=theta, sigma_z=1.702)
    probs = counts / counts.sum()
    sm = jax.nn.softmax(z)
    assert 0.5 * float(jnp.abs(probs - sm).sum()) < 0.08


# ---------------------------------------------------------------------------
# stoch_round kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(33, 70), (256, 512), (5, 1030)])
def test_stoch_round_levels_match_oracle(shape):
    x = jax.random.normal(jax.random.PRNGKey(6), shape)
    step = 2.0 / 31
    qk = ops.stoch_round(x, KEY, step=step, lo=-1, hi=1)
    qr = ops.stoch_round_reference(x, KEY, step=step, lo=-1, hi=1)
    np.testing.assert_allclose(
        np.asarray(qk), np.asarray(qr), atol=step * 1e-3
    )
    lv = (np.asarray(qk) + 1) / step
    np.testing.assert_allclose(lv, np.round(lv), atol=1e-3)


@hypothesis.given(
    step=st.sampled_from([2 / 31, 2 / 15, 0.1]),
    seed=st.integers(0, 10_000),
)
@hypothesis.settings(deadline=None, max_examples=15)
def test_stoch_round_unbiased(step, seed):
    """E[q(x)] == clip(x) — the conductance-programming invariant."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (16, 16)) * 0.8
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 300)
    qs = jnp.stack(
        [
            ops.stoch_round_reference(x, k2, step=step, lo=-1, hi=1)
            for k2 in keys
        ]
    ).mean(0)
    err = np.abs(np.asarray(qs) - np.clip(np.asarray(x), -1, 1)).max()
    assert err < step * 0.35, err


def test_stoch_round_ste_gradient():
    x = jnp.asarray([-2.0, -0.5, 0.0, 0.7, 1.5])
    g = jax.grad(
        lambda v: jnp.sum(ops.stoch_round(v[None], KEY, step=0.1, lo=-1, hi=1))
    )(x)
    np.testing.assert_allclose(np.asarray(g), [0, 1, 1, 1, 0], atol=1e-6)


# ---------------------------------------------------------------------------
# paged attention (serving decode kernel)
# ---------------------------------------------------------------------------


def _paged_case(seed, b, h, hkv, dh, n_pages, bs, w):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, bs, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, bs, hkv, dh), jnp.float32)
    # distinct pages per slot (page 0 = trash, never tabled)
    perm = jax.random.permutation(ks[3], n_pages - 1)[: b * w] + 1
    table = perm.reshape(b, w).astype(jnp.int32)
    return q, kp, vp, table


@pytest.mark.parametrize("kind,local_window", [("global", 0), ("local", 5)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_attention_kernel_matches_oracle(kind, local_window, softcap):
    """Interpret-mode kernel vs the pure-jnp gather oracle: GQA heads,
    positions mid-block, both mask kinds, with/without soft-capping."""
    from repro.kernels.paged_attention import paged_attention_pallas

    b, w, bs = 4, 3, 8
    q, kp, vp, table = _paged_case(0, b, 4, 2, 16, 16, bs, w)
    # pos exercises: block-boundary, mid-block, first token, full window
    pos = jnp.asarray([15, 12, 0, 23], jnp.int32)
    y_ref = ops.ref.paged_attention_ref(
        q, kp, vp, table, pos,
        kind=kind, local_window=local_window, softcap=softcap,
    )
    y_k = paged_attention_pallas(
        q, kp, vp, table, pos,
        kind=kind, local_window=local_window, softcap=softcap,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), atol=2e-5, rtol=1e-5
    )


def test_paged_attention_ignores_blocks_beyond_pos():
    """Pages past a slot's position must not leak into the output: poison
    them with huge values and check against a short-table oracle."""
    from repro.kernels.paged_attention import paged_attention_pallas

    b, w, bs = 2, 4, 8
    q, kp, vp, table = _paged_case(1, b, 4, 4, 16, 12, bs, w)
    pos = jnp.asarray([7, 3], jnp.int32)  # only block 0 is valid
    poison = np.asarray(table[:, 1:]).ravel()
    kp = kp.at[poison].set(1e9)
    vp = vp.at[poison].set(1e9)
    y_short = ops.ref.paged_attention_ref(
        q, kp, vp, table[:, :1], pos, kind="global"
    )
    y_k = paged_attention_pallas(
        q, kp, vp, table, pos, kind="global", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_short), atol=2e-5, rtol=1e-5
    )


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_kernels_read_lane_padded_pages(kernel, int8):
    """The engine's pools hold each head of 16 zero-padded to a 128-lane
    row: the interpret-mode kernels read such pages and agree with the
    oracle on the unpadded pages (the softmax scale stays the head's,
    16**-0.5), and the oracle reads padded pages bit for bit as it reads
    unpadded ones."""
    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.kernels.prefill_attention import paged_prefill_attention_pallas

    bs, w = 8, 3
    if kernel == "decode":
        q, kp, vp, table = _paged_case(4, 2, 4, 2, 16, 12, bs, w)
        idx = jnp.asarray([20, 9], jnp.int32)
        run = paged_attention_pallas
    else:
        q, kp, vp, table = _suffix_case(4, 6, 4, 2, 16, 12, bs, w)
        idx = jnp.asarray(8, jnp.int32)
        run = paged_prefill_attention_pallas
    ref_fn = (ops.ref.paged_attention_ref if kernel == "decode"
              else ops.ref.prefill_attention_ref)
    sc = {}
    if int8:
        kp = (kp * 40).astype(jnp.int8)
        vp = (vp * 40).astype(jnp.int8)
        sc = dict(
            k_scale=jnp.abs(kp[..., 0].astype(jnp.float32)) / 40 + 0.1,
            v_scale=jnp.abs(vp[..., 0].astype(jnp.float32)) / 40 + 0.1,
        )
    pad = ((0, 0),) * 3 + ((0, 128 - 16),)
    kpp, vpp = jnp.pad(kp, pad), jnp.pad(vp, pad)
    want = ref_fn(q, kp, vp, table, idx, **sc)
    got = run(q, kpp, vpp, table, idx, interpret=True, **sc)
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(ref_fn(q, kpp, vpp, table, idx, **sc)), np.asarray(want)
    )


def test_paged_attention_op_dispatches_off_tpu():
    """ops.paged_attention falls back to the oracle off-TPU (the serving
    hot loop must not run interpret-mode emulation)."""
    q, kp, vp, table = _paged_case(2, 2, 4, 2, 16, 8, 8, 2)
    pos = jnp.asarray([9, 4], jnp.int32)
    y = ops.paged_attention(q, kp, vp, table, pos)
    y_ref = ops.ref.paged_attention_ref(q, kp, vp, table, pos)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


# ---------------------------------------------------------------------------
# prefix-aware chunked-prefill attention (suffix-only prefill kernel)
# ---------------------------------------------------------------------------


def _suffix_case(seed, s, h, hkv, dh, n_pages, bs, w):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (s, h, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, bs, hkv, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, bs, hkv, dh), jnp.float32)
    perm = jax.random.permutation(ks[3], n_pages - 1)[:w] + 1
    table = perm.astype(jnp.int32)
    return q, kp, vp, table


@pytest.mark.parametrize("kind,local_window", [("global", 0), ("local", 5)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize(
    "s,q0",
    [
        (8, 0),    # whole-prompt chunk from position 0
        (8, 16),   # suffix chunk starting exactly at a block boundary
        (5, 16),   # ragged suffix length, block-boundary start
        (13, 8),   # ragged length spanning several blocks
    ],
)
def test_prefill_attention_kernel_matches_oracle(
    kind, local_window, softcap, s, q0
):
    """Interpret-mode kernel vs the pure-jnp oracle: GQA heads, ragged
    suffix lengths, block-boundary suffix starts, both mask kinds, with
    and without soft-capping."""
    from repro.kernels.prefill_attention import paged_prefill_attention_pallas

    bs, w = 8, 4
    q, kp, vp, table = _suffix_case(0, s, 4, 2, 16, 16, bs, w)
    y_ref = ops.ref.prefill_attention_ref(
        q, kp, vp, table, jnp.asarray(q0, jnp.int32),
        kind=kind, local_window=local_window, softcap=softcap,
    )
    y_k = paged_prefill_attention_pallas(
        q, kp, vp, table, jnp.asarray(q0, jnp.int32),
        kind=kind, local_window=local_window, softcap=softcap,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), atol=2e-5, rtol=1e-5
    )


def test_prefill_attention_kernel_matches_oracle_int8():
    """int8 pages + scale planes: the fused-dequant kernel path agrees
    with the oracle's scores-not-cache math on a mid-prompt suffix."""
    from repro.kernels.prefill_attention import paged_prefill_attention_pallas

    s, bs, w = 7, 8, 3
    q, kp, vp, table = _suffix_case(3, s, 4, 2, 16, 12, bs, w)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    kp8 = jax.random.randint(ks[0], kp.shape, -127, 128, jnp.int32).astype(
        jnp.int8
    )
    vp8 = jax.random.randint(ks[1], vp.shape, -127, 128, jnp.int32).astype(
        jnp.int8
    )
    k_scale = jnp.abs(
        jax.random.normal(ks[0], kp.shape[:3], jnp.float32)
    ) + 0.1
    v_scale = jnp.abs(
        jax.random.normal(ks[1], vp.shape[:3], jnp.float32)
    ) + 0.1
    q0 = jnp.asarray(8, jnp.int32)
    y_ref = ops.ref.prefill_attention_ref(
        q, kp8, vp8, table, q0, k_scale=k_scale, v_scale=v_scale
    )
    y_k = paged_prefill_attention_pallas(
        q, kp8, vp8, table, q0, k_scale=k_scale, v_scale=v_scale,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), atol=2e-5, rtol=1e-5
    )


@pytest.mark.parametrize("kind,local_window", [("global", 0), ("local", 11)])
@pytest.mark.parametrize("int8", [False, True])
def test_prefill_attention_kernel_tiles_queries(kind, local_window, int8):
    """A chunk longer than the query tile runs as several tiles (128 rows
    and a ragged 22 here) over GQA groups of 2: each tile restarts the
    online softmax and must agree with the oracle."""
    from repro.kernels.prefill_attention import (
        DEF_TQ, paged_prefill_attention_pallas,
    )

    s, bs, w = DEF_TQ + 22, 8, 20
    q, kp, vp, table = _suffix_case(11, s, 4, 2, 16, 24, bs, w)
    sc = {}
    if int8:
        kp = (kp * 40).astype(jnp.int8)
        vp = (vp * 40).astype(jnp.int8)
        sc = dict(
            k_scale=jnp.abs(kp[..., 0].astype(jnp.float32)) / 40 + 0.1,
            v_scale=jnp.abs(vp[..., 0].astype(jnp.float32)) / 40 + 0.1,
        )
    q0 = jnp.asarray(8, jnp.int32)
    y_ref = ops.ref.prefill_attention_ref(
        q, kp, vp, table, q0, kind=kind, local_window=local_window, **sc
    )
    y_k = paged_prefill_attention_pallas(
        q, kp, vp, table, q0, kind=kind, local_window=local_window,
        interpret=True, **sc,
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), atol=2e-5, rtol=1e-5
    )


def test_prefill_attention_suffix_matches_full_restriction():
    """Per-query independence — the property suffix-only prefill rests
    on: computing only the suffix's queries must give exactly the same
    rows the full-prompt oracle gives for those positions."""
    bs, w = 8, 3
    q, kp, vp, table = _suffix_case(5, bs * w, 4, 4, 16, 12, bs, w)
    y_full = ops.ref.prefill_attention_ref(
        q, kp, vp, table, jnp.asarray(0, jnp.int32)
    )
    suffix = q[16:]
    y_sfx = ops.ref.prefill_attention_ref(
        suffix, kp, vp, table, jnp.asarray(16, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(y_sfx), np.asarray(y_full)[16:], atol=1e-6, rtol=1e-6
    )


def test_prefill_attention_ignores_blocks_beyond_chunk():
    """Pages entirely beyond the chunk's last query must not leak into
    the output: poison them and compare against a short-table oracle."""
    from repro.kernels.prefill_attention import paged_prefill_attention_pallas

    s, bs, w = 6, 8, 4
    q, kp, vp, table = _suffix_case(7, s, 4, 4, 16, 12, bs, w)
    q0 = jnp.asarray(8, jnp.int32)  # queries cover positions 8..13
    poison = np.asarray(table[2:])
    kp = kp.at[poison].set(1e9)
    vp = vp.at[poison].set(1e9)
    y_short = ops.ref.prefill_attention_ref(q, kp, vp, table[:2], q0)
    y_k = paged_prefill_attention_pallas(
        q, kp, vp, table, q0, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_short), atol=2e-5, rtol=1e-5
    )


def test_prefill_attention_op_dispatches_off_tpu():
    """ops.paged_prefill_attention falls back to the oracle off-TPU (the
    serving prefill path must not run interpret-mode emulation)."""
    q, kp, vp, table = _suffix_case(2, 5, 4, 2, 16, 8, 8, 2)
    q0 = jnp.asarray(8, jnp.int32)
    y = ops.paged_prefill_attention(q, kp, vp, table, q0)
    y_ref = ops.ref.prefill_attention_ref(q, kp, vp, table, q0)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


# ---------------------------------------------------------------------------
# portable PRNG quality
# ---------------------------------------------------------------------------


def test_prng_gaussian_moments():
    idx = jnp.arange(200_000, dtype=jnp.uint32)
    g = prng.gaussian(idx, jnp.uint32(7))
    assert abs(float(g.mean())) < 0.01
    assert abs(float(g.std()) - 1.0) < 0.01
    kurt = float(((g - g.mean()) ** 4).mean() / g.std() ** 4)
    assert abs(kurt - 3.0) < 0.1


def test_prng_streams_decorrelated():
    idx = jnp.arange(100_000, dtype=jnp.uint32)
    a = prng.gaussian(idx, jnp.uint32(1))
    b = prng.gaussian(idx, jnp.uint32(2))
    corr = float(jnp.corrcoef(a, b)[0, 1])
    assert abs(corr) < 0.02
    # sequential correlation within one stream
    corr2 = float(jnp.corrcoef(a[:-1], a[1:])[0, 1])
    assert abs(corr2) < 0.02


# ---------------------------------------------------------------------------
# int8 paged attention (fused-dequant serving decode kernel)
# ---------------------------------------------------------------------------


def _int8_paged_case(seed, b, h, hkv, dh, n_pages, bs, w):
    q, kp, vp, table = _paged_case(seed, b, h, hkv, dh, n_pages, bs, w)
    k8, ks = ops.quantize_kv_int8(kp, jnp.uint32(seed))
    v8, vs = ops.quantize_kv_int8(vp, jnp.uint32(seed + 77))
    return q, kp, vp, k8, v8, ks, vs, table


@pytest.mark.parametrize("kind,local_window", [("global", 0), ("local", 5)])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_int8_paged_attention_kernel_matches_oracle(
    kind, local_window, softcap
):
    """Interpret-mode fused-dequant kernel vs the int8 oracle: int8 codes +
    scale planes in, scales applied to scores/weights in VMEM."""
    from repro.kernels.paged_attention import paged_attention_pallas

    b, w, bs = 4, 3, 8
    q, _, _, k8, v8, ks, vs, table = _int8_paged_case(
        3, b, 4, 2, 16, 16, bs, w
    )
    pos = jnp.asarray([15, 12, 0, 23], jnp.int32)
    y_ref = ops.ref.paged_attention_ref(
        q, k8, v8, table, pos,
        kind=kind, local_window=local_window, softcap=softcap,
        k_scale=ks, v_scale=vs,
    )
    y_k = paged_attention_pallas(
        q, k8, v8, table, pos,
        kind=kind, local_window=local_window, softcap=softcap,
        k_scale=ks, v_scale=vs, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), atol=2e-5, rtol=2e-5
    )


def test_int8_paged_attention_close_to_full_precision():
    """The quantized path is an approximation of the f32 pool with bounded
    error: per-row max-abs scales keep the relative readout error small."""
    q, kp, vp, k8, v8, ks, vs, table = _int8_paged_case(
        4, 2, 4, 2, 16, 8, 8, 2
    )
    pos = jnp.asarray([9, 4], jnp.int32)
    y_fp = ops.ref.paged_attention_ref(q, kp, vp, table, pos)
    y_i8 = ops.ref.paged_attention_ref(
        q, k8, v8, table, pos, k_scale=ks, v_scale=vs
    )
    rel = float(
        jnp.max(jnp.abs(y_i8 - y_fp)) / jnp.max(jnp.abs(y_fp))
    )
    assert rel < 0.05, rel


def test_int8_paged_attention_op_dispatches_off_tpu():
    q, _, _, k8, v8, ks, vs, table = _int8_paged_case(5, 2, 4, 2, 16, 8, 8, 2)
    pos = jnp.asarray([9, 4], jnp.int32)
    y = ops.paged_attention(q, k8, v8, table, pos, k_scale=ks, v_scale=vs)
    y_ref = ops.ref.paged_attention_ref(
        q, k8, v8, table, pos, k_scale=ks, v_scale=vs
    )
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


# ---------------------------------------------------------------------------
# quantize_kv_int8 (stochastic-rounded cache quantizer)
# ---------------------------------------------------------------------------


def test_quantize_kv_int8_error_bounded_by_scale_step():
    """Stochastic rounding moves each element to an adjacent grid level:
    |dequant - x| <= scale/127 elementwise, codes within [-127, 127]."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 3, 32), jnp.float32)
    codes, scale = ops.quantize_kv_int8(x, jnp.uint32(11))
    assert codes.dtype == jnp.int8
    assert scale.shape == x.shape[:-1]
    step = scale[..., None] / 127.0
    deq = codes.astype(jnp.float32) * step
    assert bool(jnp.all(jnp.abs(deq - x) <= step + 1e-6))
    assert int(jnp.max(jnp.abs(codes.astype(jnp.int32)))) <= 127


def test_quantize_kv_int8_unbiased_over_seeds():
    """E[dequant] ~= x over stochastic-rounding seeds — the paper's
    unbiased conductance-programming property on the cache path.  With 256
    seeds the worst-case element bias stays well inside the ~4-sigma band
    of an unbiased rounder (sigma <= 0.5 step / sqrt(256))."""
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64), jnp.float32)
    _, scale = ops.quantize_kv_int8(x, jnp.uint32(0))
    step = scale[..., None] / 127.0
    acc = jnp.zeros_like(x)
    n = 256
    for s in range(n):
        codes, _ = ops.quantize_kv_int8(x, jnp.uint32(s))
        acc = acc + codes.astype(jnp.float32) * step
    bias_steps = jnp.max(jnp.abs(acc / n - x) / step)
    assert float(bias_steps) < 0.2, float(bias_steps)


def test_quantize_kv_int8_seed_varies_rounding():
    """Different seeds must draw different rounding decisions (the decode
    step feeds a fresh per-(step, layer) seed so cache noise never
    repeats)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 32), jnp.float32)
    c0, _ = ops.quantize_kv_int8(x, jnp.uint32(0))
    c1, _ = ops.quantize_kv_int8(x, jnp.uint32(1))
    assert bool(jnp.any(c0 != c1))
