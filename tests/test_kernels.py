"""Per-kernel allclose sweeps against the pure-jnp oracles.

The CPU has no Mosaic compiler, so every call asks for interpret mode
explicitly; ``tests/test_tpu_compile.py`` compiles the same kernels for a
described TPU."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)

INT_SHAPES = [(8, 128), (16, 256), (8, 512), (24, 384), (64, 128)]
INT_DTYPES = [np.int32, np.int8]


def _rand(shape, dtype):
    if dtype == np.int8:
        return jnp.asarray(RNG.integers(-128, 128, size=shape, dtype=dtype))
    return jnp.asarray(RNG.integers(-2**30, 2**30, size=shape, dtype=dtype))


@pytest.mark.parametrize("n_ops", [2, 3, 7,
                                   pytest.param(48, marks=pytest.mark.slow)])
@pytest.mark.parametrize("op", ["and", "or", "xor", "nand", "nor"])
def test_mws_sweep(n_ops, op):
    stack = _rand((n_ops, 16, 256), np.int32)
    got = ops.mws_bitwise(stack, op, interpret=True)
    want = ref.ref_mws(stack, op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", INT_SHAPES)
@pytest.mark.parametrize("dtype", INT_DTYPES)
def test_bitserial_add_sweep(shape, dtype):
    a, b = _rand(shape, dtype), _rand(shape, dtype)
    np.testing.assert_array_equal(
        np.asarray(ops.bitserial_add(a, b, interpret=True)),
        np.asarray(ref.ref_bitserial_add(a, b)))


@pytest.mark.parametrize("shape", INT_SHAPES[:3])
@pytest.mark.parametrize("dtype", INT_DTYPES)
def test_bitserial_mul_sweep(shape, dtype):
    a, b = _rand(shape, dtype), _rand(shape, dtype)
    np.testing.assert_array_equal(
        np.asarray(ops.bitserial_mul(a, b, interpret=True)),
        np.asarray(ref.ref_bitserial_mul(a, b)))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("shape", INT_SHAPES[:3])
def test_shift_add_sweep(bits, shape):
    a, b = _rand(shape, np.int32), _rand(shape, np.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.shift_add_mul(a, b, bits=bits, interpret=True)),
        np.asarray(ref.ref_shift_add_mul(a, b, bits)))


@pytest.mark.parametrize("m,k,n", [
    (32, 64, 32),
    pytest.param(128, 128, 128, marks=pytest.mark.slow),
    pytest.param(64, 96, 160, marks=pytest.mark.slow),
    (16, 32, 48)])
def test_int8_matmul_sweep(m, k, n):
    a = jnp.asarray(RNG.integers(-128, 128, size=(m, k), dtype=np.int8))
    b = jnp.asarray(RNG.integers(-128, 128, size=(k, n), dtype=np.int8))
    np.testing.assert_array_equal(
        np.asarray(ops.int8_matmul(a, b, interpret=True)),
        np.asarray(ref.ref_int8_matmul(a, b)))


@pytest.mark.parametrize("h,s,d", [(2, 64, 32), (1, 128, 64), (4, 32, 16)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [np.float32])
def test_attention_sweep(h, s, d, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(h, s, d)).astype(dtype))
    k = jnp.asarray(RNG.normal(size=(h, s, d)).astype(dtype))
    v = jnp.asarray(RNG.normal(size=(h, s, d)).astype(dtype))
    got = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_attention_cross_lengths():
    q = jnp.asarray(RNG.normal(size=(2, 32, 32)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(2, 128, 32)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(2, 128, 32)).astype(np.float32))
    got = ops.flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("wpr", [1, 2, 4])
@pytest.mark.parametrize("rows", [8, 24])
def test_search_kernel_sweep(wpr, rows):
    """§7 extensibility: in-flash exact-match search vs oracle."""
    words = 32
    stack = _rand((rows, words), np.int32)
    # plant known matches
    stack = stack.at[3, 0:wpr].set(jnp.arange(wpr))
    query = jnp.arange(wpr, dtype=jnp.int32)
    got = ops.search_pages(stack, query, interpret=True)
    want = ref.ref_search(stack, query)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert bool(np.asarray(want)[3, 0])


def test_compiled_mode_is_the_default():
    """Without ``interpret=True`` a kernel is compiled for the backend; the
    CPU has no Mosaic compiler, so it fails instead of interpreting."""
    stack = _rand((2, 8, 128), np.int32)
    with pytest.raises(Exception, match="(?i)interpret"):
        ops.mws_bitwise(stack, "and")


def test_search_routes_to_ifp():
    """The new 'search' op is first-class: the cost function routes
    flash-resident searches to the in-flash match primitive."""
    from repro.core.cost import SystemView
    from repro.core.isa import Location, Resource, VectorInstr
    from repro.core.policies import make_policy
    from repro.hw.ssd_spec import DEFAULT_SSD
    pol = make_policy("conduit", DEFAULT_SSD)
    ins = VectorInstr(iid=0, op="search", vlen=DEFAULT_SSD.page_size,
                      elem_bytes=1, srcs=(0,), dst=1)
    view = SystemView(0.0, lambda r: 0.0, lambda i: 0.0,
                      lambda p: Location.FLASH)
    d = pol.select(ins, view)
    assert d.resource == Resource.IFP
    assert ins.native(Resource.IFP) == "ifp.mws_match"
