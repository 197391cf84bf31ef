"""Round-trip coverage for the wire-format bit packing
(core/quantize/packing.py) across code widths and odd lengths, plus
hypothesis property tests over arbitrary contents/lengths (skipped
with a clear reason when hypothesis is not installed)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize.packing import (pack_codes, pack_signs,
                                         unpack_codes, unpack_signs)

from _hypothesis_compat import given, settings, st


@pytest.mark.parametrize("d", [1, 31, 32, 33, 100, 127, 128, 129, 1000])
def test_sign_roundtrip(d):
    rng = np.random.default_rng(d)
    x = rng.standard_normal(d).astype(np.float32)
    x[rng.random(d) < 0.1] = 0.0          # sign(0) must decode as -1
    words = pack_signs(jnp.asarray(x))
    assert words.shape == (-(-d // 32),)
    assert words.dtype == jnp.uint32
    signs = np.asarray(unpack_signs(words, d))
    np.testing.assert_array_equal(signs, np.where(x > 0, 1.0, -1.0))


@pytest.mark.parametrize("b", [2, 4, 8, 16])
@pytest.mark.parametrize("n", [1, 3, 7, 16, 17, 100])
def test_code_roundtrip(b, n):
    rng = np.random.default_rng(b * 1000 + n)
    codes = rng.integers(0, 2 ** b, n).astype(np.uint32)
    words = pack_codes(jnp.asarray(codes), b)
    per = 32 // b
    assert words.shape == (-(-n // per),)
    out = np.asarray(unpack_codes(words, b, n))
    np.testing.assert_array_equal(out, codes)


@pytest.mark.parametrize("b", [0, 3, 5, 7, 24, 33])
def test_code_width_must_divide_32(b):
    """Widths that do not divide 32 would silently mis-split words;
    both pack and unpack must reject them up front."""
    with pytest.raises(ValueError, match="divide 32"):
        pack_codes(jnp.zeros(4, jnp.uint32), b)
    with pytest.raises(ValueError, match="divide 32"):
        unpack_codes(jnp.zeros(1, jnp.uint32), b, 4)


# ------------------------------------------------ edge / degenerate cases
def test_sign_roundtrip_zero_length():
    words = pack_signs(jnp.zeros((0,), jnp.float32))
    assert words.shape == (0,) and words.dtype == jnp.uint32
    assert unpack_signs(words, 0).shape == (0,)


@pytest.mark.parametrize("b", [2, 4, 8, 16])
def test_code_roundtrip_zero_length(b):
    words = pack_codes(jnp.zeros((0,), jnp.uint32), b)
    assert words.shape == (0,) and words.dtype == jnp.uint32
    assert unpack_codes(words, b, 0).shape == (0,)


def test_all_zero_sign_vector_decodes_minus_one():
    """sign(0) transmits bit 0 and must decode as -1 (eq. 7's
    x > 0 convention), for a full word and a ragged tail."""
    for d in (32, 45):
        out = np.asarray(unpack_signs(pack_signs(jnp.zeros(d)), d))
        np.testing.assert_array_equal(out, -np.ones(d, np.float32))


def expected_signs(x: np.ndarray) -> np.ndarray:
    """The sign-plane contract: bit 1 (decoded +1) iff x > 0 after XLA's
    flush of subnormals to zero, on the CPU as on the TPU.  A positive
    subnormal therefore packs as "not positive" (-1)."""
    normal_pos = x >= np.finfo(np.float32).tiny
    return np.where(normal_pos, 1.0, -1.0)


@pytest.mark.parametrize("x", [1.4e-45, -1.4e-45, 1e-39,
                               float(np.finfo(np.float32).tiny)])
def test_sign_of_subnormal_is_not_positive(x):
    """Subnormals flush to zero, so their sign bit is 0; the smallest
    normal float stays positive."""
    xs = np.asarray([x, 1.0, -1.0], np.float32)
    out = np.asarray(unpack_signs(pack_signs(jnp.asarray(xs)), 3))
    np.testing.assert_array_equal(out, expected_signs(xs))
    assert out[0] == (1.0 if x >= np.finfo(np.float32).tiny else -1.0)


# -------------------------------------------------- hypothesis properties
@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(allow_nan=False, allow_infinity=False,
                          width=32),
                min_size=0, max_size=200))
def test_sign_roundtrip_property(xs):
    """pack/unpack signs is a roundtrip of the sign-plane contract
    (positive normal floats -> +1, everything else -> -1) for ANY
    finite float contents at ANY length (word-aligned or not)."""
    x = np.asarray(xs, np.float32)
    words = pack_signs(jnp.asarray(x))
    assert words.shape == (-(-len(xs) // 32),)
    out = np.asarray(unpack_signs(words, len(xs)))
    np.testing.assert_array_equal(out, expected_signs(x))


@settings(max_examples=50, deadline=None)
@given(st.sampled_from([2, 4, 8, 16]), st.integers(0, 300),
       st.randoms(use_true_random=False))
def test_code_roundtrip_property(b, n, rnd):
    """pack/unpack codes is a roundtrip for every supported width and
    length, including non-word-aligned tails."""
    codes = np.asarray([rnd.randrange(2 ** b) for _ in range(n)],
                       np.uint32)
    words = pack_codes(jnp.asarray(codes), b)
    per = 32 // b
    assert words.shape == (-(-n // per),)
    assert words.dtype == jnp.uint32
    out = np.asarray(unpack_codes(words, b, n))
    np.testing.assert_array_equal(out, codes)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from([2, 4, 8, 16]), st.integers(1, 100))
def test_code_pack_all_zero_property(b, n):
    """All-zero codes pack to all-zero words and roundtrip."""
    words = pack_codes(jnp.zeros(n, jnp.uint32), b)
    assert not np.asarray(words).any()
    np.testing.assert_array_equal(np.asarray(unpack_codes(words, b, n)),
                                  np.zeros(n, np.uint32))


@pytest.mark.parametrize("G,d", [(2, 25600), (3, 4096), (2, 128),
                                 (5, 33000), (8, 262144)])
def test_packed_sign_weighted_sum_blocking(G, d):
    """The stacked G-plane launch must block correctly for every
    (G, d) window — including per-plane rows <= 256 with G*rows not a
    multiple of 256 (regression: AssertionError in signpack)."""
    from repro.kernels.ops import packed_sign_weighted_sum

    rng = np.random.default_rng(G * d)
    x = rng.standard_normal((G, d)).astype(np.float32)
    scales = rng.uniform(0.1, 1.0, G).astype(np.float32)
    out = np.asarray(packed_sign_weighted_sum(jnp.asarray(x),
                                              jnp.asarray(scales)))
    ref = (np.where(x > 0, 1.0, -1.0) * scales[:, None]).sum(0)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


def test_pack_signs_matches_pallas_signpack():
    """The jnp reference and the Pallas kernel produce identical
    words on a 128-aligned vector."""
    from repro.kernels.ops import signpack_op

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(4096), jnp.float32)
    np.testing.assert_array_equal(np.asarray(pack_signs(x)),
                                  np.asarray(signpack_op(x)))
