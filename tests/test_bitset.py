"""uint32 bitset primitives vs numpy oracles (ops/bitset)."""

import numpy as np

from foundationdb_tpu.ops.bitset import (
    or_matvec_u32,
    pack_bits_u32,
    unpack_bits_u32,
)


def test_pack_unpack_roundtrip(rng):
    for shape in [(32,), (64,), (4, 96), (3, 5, 32)]:
        m = rng.random(shape) < 0.4
        p = np.asarray(pack_bits_u32(m))
        assert p.dtype == np.uint32
        assert p.shape == (*shape[:-1], shape[-1] // 32)
        back = np.asarray(unpack_bits_u32(p, shape[-1]))
        assert (back == m).all()


def test_pack_bit_order(rng):
    """Bit c of word w encodes element w*32 + c (little-endian lanes)."""
    m = np.zeros(64, bool)
    m[0] = m[33] = True
    p = np.asarray(pack_bits_u32(m))
    assert p[0] == 1 and p[1] == 2


def test_or_matvec_matches_dense(rng):
    rows = rng.random((40, 128)) < 0.1
    vec = rng.random(128) < 0.2
    got = np.asarray(or_matvec_u32(pack_bits_u32(rows), pack_bits_u32(vec)))
    want = (rows @ vec) > 0
    assert (got == want).all()
    # All-zero vector never hits.
    zero = np.zeros(128, bool)
    got0 = np.asarray(or_matvec_u32(pack_bits_u32(rows), pack_bits_u32(zero)))
    assert not got0.any()


def test_packed_accept_variants_match_dense(rng):
    """_wave_accept_packed ≡ its dense twin ≡ the sequential python oracle
    on a random predecessor matrix."""
    import jax.numpy as jnp

    from foundationdb_tpu.models import conflict_kernel as ck

    g = 128
    m = np.asarray(rng.random((g, g)) < 0.05)
    base = np.asarray(rng.random(g) < 0.9)
    p = pack_bits_u32(jnp.asarray(m))

    acc = np.zeros(g, bool)
    for i in range(g):
        if base[i]:
            acc[i] = not (m[i, :i] & acc[:i]).any()

    wave_p = np.asarray(ck._wave_accept_packed(jnp.asarray(base), p))
    wave_d = np.asarray(ck._wave_accept(jnp.asarray(base), jnp.asarray(m)))
    assert (wave_p == acc).all()
    assert (wave_d == acc).all()


def test_pack_loser_mask_roundtrip(rng):
    import jax.numpy as jnp

    from foundationdb_tpu.models import conflict_kernel as ck

    losers = rng.random((17, 8)) < 0.3
    packed = np.asarray(ck.pack_loser_mask(jnp.asarray(losers)))
    assert packed.dtype == np.uint32
    back = ((packed[:, None] >> np.arange(8, dtype=np.uint32)) & 1).astype(bool)
    assert (back == losers).all()
    # R > 32 degrades to the bool mask unchanged.
    wide = rng.random((4, 40)) < 0.5
    out = np.asarray(ck.pack_loser_mask(jnp.asarray(wide)))
    assert out.dtype == np.bool_ and (out == wide).all()
