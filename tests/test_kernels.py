"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the pure-jnp
ref.py oracle for every kernel."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.blockhash import ops as bh_ops, ref as bh_ref
from repro.kernels.flash_attention import kernel as fa_k, ref as fa_ref
from repro.kernels.ssd import kernel as ssd_k, ref as ssd_ref
from repro.kernels.wkv6 import kernel as wkv_k, ref as wkv_ref


@pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,D,causal,window", [
    (2, 256, 256, 4, 2, 64, True, 0),
    (1, 512, 512, 8, 8, 128, True, 0),
    (2, 256, 256, 4, 4, 64, False, 0),
    (1, 512, 512, 4, 2, 64, True, 128),
    (1, 256, 512, 4, 1, 64, False, 0),  # cross-ish: Skv != Sq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Skv, Hq, Hkv, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    out = fa_k.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   interpret=True)
    want = fa_ref.attention(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_grad_matches_ref():
    from repro.kernels.flash_attention import ops as fa_ops
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))

    def f_kernel(q, k, v):
        return jnp.sum(fa_ops.flash_attention(q, k, v, True, 0, 0.0, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(fa_ref.attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(f_kernel)(q, k, v)
    g2 = jax.grad(f_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-3)


@pytest.mark.parametrize("B,S,H,K,V,C", [
    (2, 64, 3, 16, 16, 16),
    (1, 128, 2, 32, 32, 32),
    (1, 64, 1, 8, 8, 64),  # single chunk
])
def test_wkv6(B, S, H, K, V, C):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    r = jax.random.normal(ks[0], (B, S, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, V))
    w = jax.random.normal(ks[3], (B, S, H, K)) * 0.3
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, K, V)) * 0.1
    y1, st1 = wkv_ref.wkv6(r, k, v, w, u, s0, chunk=C)
    y2, st2 = wkv_k.wkv6_chunked(r, k, v, w, u, s0, chunk=C, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=1e-4)


def test_wkv6_chunked_equals_stepwise():
    """Chunked scan == token-by-token recurrence (cross-oracle check)."""
    from repro.models.rwkv import wkv6_step
    B, S, H, K = 1, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = jax.random.normal(ks[0], (B, S, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, K))
    w = jax.random.normal(ks[3], (B, S, H, K)) * 0.3
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    s = jnp.zeros((B, H, K, K))
    y_chunk, s_chunk = wkv_ref.wkv6(r, k, v, w, u, s, chunk=8)
    ys = []
    st = s
    for t in range(S):
        y, st = wkv6_step(r[:, t], k[:, t], v[:, t], w[:, t], u, st)
        ys.append(y)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_chunk), np.asarray(st), atol=1e-4)


@pytest.mark.parametrize("b,S,H,P,N,C", [
    (2, 128, 3, 16, 8, 32),
    (1, 256, 2, 64, 64, 128),
    (1, 64, 1, 8, 8, 64),
])
def test_ssd(b, S, H, P, N, C):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    B = jax.random.normal(ks[2], (b, S, N)) * 0.5
    Cm = jax.random.normal(ks[3], (b, S, N)) * 0.5
    A_log = jax.random.normal(ks[4], (H,)) * 0.3
    D = jnp.ones((H,))
    h0 = jax.random.normal(ks[5], (b, H, P, N)) * 0.1
    y1, st1 = ssd_ref.ssd(x, dt, B, Cm, A_log, D, h0, chunk=C)
    y2, st2 = ssd_k.ssd_chunked(x, dt, B, Cm, A_log, D, h0, chunk=C,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=2e-4)


def test_ssd_chunked_equals_stepwise():
    from repro.models.mamba2 import ssd_step
    b, S, H, P, N = 1, 32, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    B = jax.random.normal(ks[2], (b, S, N)) * 0.5
    C = jax.random.normal(ks[3], (b, S, N)) * 0.5
    A_log = jax.random.normal(ks[4], (H,)) * 0.3
    D = jnp.ones((H,))
    h = jnp.zeros((b, H, P, N))
    y_chunk, h_chunk = ssd_ref.ssd(x, dt, B, C, A_log, D, h, chunk=8)
    ys = []
    st = h
    for t in range(S):
        y, st = ssd_step(x[:, t], dt[:, t], B[:, t], C[:, t], A_log, D, st)
        ys.append(y)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(st), atol=1e-4)


@pytest.mark.parametrize("nbytes", [16, 512, 4096, 4093])
def test_blockhash(nbytes):
    data = os.urandom(nbytes)
    assert bh_ops.checksum(data, interpret=True) == bh_ref.blockhash_np(data)


@pytest.mark.parametrize("nbytes", [0, 3, 4096, 9 * 4096 + 5])
def test_blockhash_reference_matches_plain_loop(nbytes):
    """The vectorized reference (doubling powers, any base) against
    Horner's rule word by word, so the kernel and its reference cannot
    share a wrong power table."""
    data = np.random.default_rng(nbytes).bytes(nbytes)
    words = np.frombuffer(data + b"\0" * (-nbytes % 4), np.uint32)
    h = 0
    for w in words.tolist():
        h = (h * int(bh_ref.PRIME) + w) & 0xFFFFFFFF
    assert bh_ref.blockhash_np(data) == h
    base = pow(int(bh_ref.PRIME), 1024, 1 << 32)
    assert bh_ref.powers(5, base).tolist() == [
        pow(base, e, 1 << 32) for e in range(4, -1, -1)]


def test_blockhash_detects_corruption():
    data = bytearray(os.urandom(4096))
    h = bh_ops.checksum(bytes(data), interpret=True)
    data[100] ^= 0xFF
    assert bh_ops.checksum(bytes(data), interpret=True) != h


def test_blockhash_batch():
    blocks = [os.urandom(4096) for _ in range(5)]
    got = bh_ops.checksum_batch(blocks, interpret=True)
    want = [bh_ref.blockhash_np(b) for b in blocks]
    assert got == want


@pytest.mark.parametrize("n", range(1, 66))
def test_blockhash_batch_bucketed(n):
    """Every journal-commit size pads to a bucket of 8..128 rows and still
    hashes each block exactly like the host reference."""
    rng = np.random.default_rng(n)
    blocks = [rng.bytes(4096) for _ in range(n)]
    rows, block_rows = bh_ops.bucket(n)
    assert rows >= n and rows % 8 == 0 and rows in (8, 16, 32, 64, 128)
    assert bh_ops.checksum_batch(blocks, interpret=True) == \
        [bh_ref.blockhash_np(b) for b in blocks]


def test_blockhash_batch_ragged_lengths():
    blocks = [os.urandom(k) for k in (0, 1, 5, 4093, 4096, 9000)]
    assert bh_ops.checksum_batch(blocks, interpret=True) == \
        [bh_ref.blockhash_np(b) for b in blocks]


@pytest.mark.parametrize("nbytes", [
    4097, 3 * 4096 + 7, 64 * 4096, 300 * 4096 + 3])
def test_blockhash_long_buffer_tiled(nbytes):
    """A buffer longer than one row is hashed in 4 KiB rows (several grid
    steps at 300 rows) and the row hashes combined — bit-exact."""
    data = np.random.default_rng(nbytes).bytes(nbytes)
    assert bh_ops.checksum(data, interpret=True) == bh_ref.blockhash_np(data)


def test_blockhash_bucket_sizes():
    assert [bh_ops.bucket(n) for n in (1, 63, 64, 65, 256, 257)] == [
        (8, 8), (64, 64), (64, 64), (128, 128), (256, 256), (512, 256)]
    for n in (1000, 8191, 27648, 1 << 20):
        rows, block_rows = bh_ops.bucket(n)
        assert rows % block_rows == 0 and n <= rows < n * 9 / 8 + 256


def test_kernel_binding_raises_when_tpu_probe_fails(monkeypatch):
    """On a TPU a kernel that fails its bind-time probe is an error, never
    a silent CRC32 binding."""
    from repro.core.services import kernel_binding
    from repro.fs.blockdev import MemBlockDevice

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bh_ops, "checksum", lambda data, interpret: 0)
    monkeypatch.setattr(bh_ops, "checksum_batch",
                        lambda blocks, interpret: [0] * len(blocks))
    with pytest.raises(RuntimeError, match="probe"):
        kernel_binding(MemBlockDevice(64))

    def broken(data, interpret):
        raise NotImplementedError("kernel does not lower")

    monkeypatch.setattr(bh_ops, "checksum", broken)
    with pytest.raises(NotImplementedError):
        kernel_binding(MemBlockDevice(64))


def test_kernel_binding_records_checksum_impl(monkeypatch):
    from repro.core.services import kernel_binding, userspace_binding
    from repro.fs.blockdev import MemBlockDevice

    monkeypatch.delenv("REPRO_FORCE_PALLAS_CHECKSUM", raising=False)
    assert kernel_binding(MemBlockDevice(64)).checksum_impl == "crc32"
    assert userspace_binding(MemBlockDevice(64)).checksum_impl == "crc32"
    monkeypatch.setenv("REPRO_FORCE_PALLAS_CHECKSUM", "1")
    ks = kernel_binding(MemBlockDevice(64))
    assert ks.checksum_impl == "blockhash-interpret"
    block = os.urandom(4096)
    assert ks.checksum_batch([block]) == [bh_ref.blockhash_np(block)]


def test_compiler_params_compat_shim():
    """Every kernel builds the installed jax's ``pltpu.CompilerParams``
    directly, and it constructs with the kwargs the kernels pass (a field
    rename breaks loudly here)."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.blockhash import kernel as bh_k
    for mod in (fa_k, wkv_k, ssd_k, bh_k):
        assert mod.pltpu.CompilerParams is pltpu.CompilerParams
    for sem in (("parallel",), ("parallel", "parallel", "arbitrary"),
                ("parallel", "parallel", "parallel", "arbitrary")):
        pltpu.CompilerParams(dimension_semantics=sem)
