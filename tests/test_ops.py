"""Pallas segment-sum kernels (ndstpu.ops.segsum) vs numpy oracle.

Runs the pallas interpreter on CPU; the real lowering targets the MXU
(one-hot matmul formulation of grouped aggregation)."""

import numpy as np
import pytest

import jax.numpy as jnp

from ndstpu.ops import segsum


@pytest.mark.parametrize("n,s", [(1000, 7), (4096, 300), (513, 1)])
def test_segment_sum_f32(n, s):
    rng = np.random.RandomState(5)
    vals = rng.uniform(-100, 100, n).astype(np.float32)
    gid = rng.randint(0, s, n).astype(np.int32)
    mask = rng.rand(n) < 0.8
    got = np.asarray(segsum.segment_sum_f32(
        jnp.asarray(vals), jnp.asarray(gid), jnp.asarray(mask), s,
        block_rows=256, block_segs=128, interpret=True))
    want = np.zeros(s, np.float64)
    np.add.at(want, gid[mask], vals[mask].astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-2)


@pytest.mark.parametrize("n,s", [(2048, 11), (4096, 500)])
def test_segment_sum_decimal_exact(n, s):
    rng = np.random.RandomState(7)
    # signed cents incl. values far above f32's exact-integer range
    vals = rng.randint(-10**12, 10**12, n).astype(np.int64)
    gid = rng.randint(0, s, n).astype(np.int32)
    mask = rng.rand(n) < 0.9
    sums, counts = segsum.segment_sum_decimal(
        jnp.asarray(vals), jnp.asarray(gid), jnp.asarray(mask), s,
        block_rows=256, block_segs=128, interpret=True)
    want = np.zeros(s, np.int64)
    np.add.at(want, gid[mask], vals[mask])
    wantc = np.bincount(gid[mask], minlength=s).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(sums), want)   # EXACT
    np.testing.assert_array_equal(np.asarray(counts), wantc)


def test_segment_sum_decimal_empty_mask():
    n, s = 512, 9
    vals = np.arange(n, dtype=np.int64)
    gid = (np.arange(n) % s).astype(np.int32)
    mask = np.zeros(n, bool)
    sums, counts = segsum.segment_sum_decimal(
        jnp.asarray(vals), jnp.asarray(gid), jnp.asarray(mask), s,
        block_rows=256, block_segs=128, interpret=True)
    assert np.asarray(sums).tolist() == [0] * s
    assert np.asarray(counts).tolist() == [0] * s


# -- keycmp: which of K unique keys is each row's ---------------------------


@pytest.mark.parametrize("n,k,alive", [(256, 256, 0), (256, 256, 256),
                                       (1024, 512, 257), (8192, 256, 40),
                                       (131072, 272, 200),
                                       # capacities no power of two (a
                                       # UNION ALL's): padded to blocks
                                       (256 + 8192, 256, 100),
                                       ((1 << 18) + (1 << 15), 256, 60),
                                       (200, 256, 3)])
def test_keycmp_match_rows(n, k, alive):
    """The kernel (interpreted) against a table over the key domain:
    unused key slots (-2) and NULL / dead probe keys (-1) match nothing."""
    from ndstpu.ops import keycmp
    rng = np.random.RandomState(n + alive)
    domain = 5000
    keys = np.full(k, -2, np.int32)
    keys[:alive] = rng.choice(domain, alive, replace=False)
    rows = rng.randint(0, 10 ** 6, k).astype(np.int32)
    x = rng.randint(-1, domain, n).astype(np.int32)
    x[: min(alive, n)] = keys[: min(alive, n)]      # every key asked for
    got = np.asarray(keycmp.match_rows(
        jnp.asarray(x), jnp.asarray(keys), jnp.asarray(rows),
        interpret=True))
    lut = np.full(domain, -1, np.int32)
    lut[keys[:alive]] = rows[:alive]
    want = np.where(x >= 0, lut[np.clip(x, 0, None)], -1)
    np.testing.assert_array_equal(got, want)


def test_keycmp_rejects_untiled_keys():
    from ndstpu.ops import keycmp
    ok = jnp.zeros(256, jnp.int32)
    with pytest.raises(ValueError):
        keycmp.match_rows(ok, ok[:5], ok[:5], interpret=True)


# The chip's own compiler, for a chip that is described and not attached:
# what the interpreter cannot refuse (tiling, scalar memory).  Nothing runs.

@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n,k", [(256, 256), (1 << 20, 8192),
                                 (1 << 22, 512), (1 << 22, 32768),
                                 (256 + 8192, 256),
                                 ((1 << 20) + (1 << 17), 512)])
def test_keycmp_compiles_for_v5e(one_chip, n, k):
    import jax
    from ndstpu.engine import jaxexec
    from ndstpu.ops import keycmp
    assert k <= jaxexec._COMPARE_MAX_KEYS
    x = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    kk = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    compiled = keycmp.match_rows.lower(x, kk, kk).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # nothing of [K, n] is materialised
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n
