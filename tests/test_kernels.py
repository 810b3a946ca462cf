"""Golden-parity tests: JAX kernels vs NumPy twins (SURVEY.md §4 strategy 1).

The twins in ops/numpy_ref.py mirror reference formats/spectra.py semantics in
float64; the kernels run in float32 on device. Pure index-permutation ops must
match exactly; reduction-based ops to float32 tolerances.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from pypulsar_tpu.ops import kernels, numpy_ref
from pypulsar_tpu.core.spectra import Spectra

RNG = np.random.RandomState(42)


def make_data(C=16, T=128):
    return RNG.randn(C, T).astype(np.float32)


def make_freqs(C=16, fch1=1500.0, foff=-1.0):
    return (fch1 + foff * np.arange(C)).astype(np.float64)


@pytest.mark.parametrize("padval", [0, 3.5, "mean", "median", "rotate"])
def test_shift_channels_parity(padval):
    data = make_data()
    bins = RNG.randint(-50, 50, size=16)
    ref = numpy_ref.shift_channels(data, bins, padval)
    got = np.asarray(kernels.shift_channels(jnp.asarray(data), jnp.asarray(bins), padval))
    if padval == "rotate" or isinstance(padval, (int, float)):
        # pure permutation + constant fill: exact
        np.testing.assert_array_equal(got.astype(np.float64), ref)
    else:
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dm", [0.0, 12.3, 100.0, 496.9])
def test_dedisperse_parity(dm):
    data = make_data()
    freqs = make_freqs()
    ref = numpy_ref.dedisperse(data, freqs, 64e-6, dm)
    got = np.asarray(
        kernels.dedisperse_with_bins(
            jnp.asarray(data), jnp.asarray(numpy_ref.bin_delays(dm, freqs, 64e-6))
        )
    )
    np.testing.assert_array_equal(got.astype(np.float64), ref)


def test_bin_delays_device_vs_host():
    # device f32 delay math must agree with host f64 for realistic params
    freqs = make_freqs(1024, 1500.0, -0.3)
    for dm in [0.0, 3.7, 56.8, 212.0, 499.5]:
        host = numpy_ref.bin_delays(dm, freqs, 64e-6)
        dev = np.asarray(kernels.bin_delays(dm, jnp.asarray(freqs, jnp.float32), 64e-6))
        # f32 rounding can flip a bin near .5 boundaries; allow <=1 bin on <1% of chans
        diff = np.abs(host - dev)
        assert diff.max() <= 1
        assert (diff > 0).mean() < 0.01


@pytest.mark.parametrize("subdm", [None, 50.0])
def test_subband_parity(subdm):
    data = make_data(16, 128)
    freqs = make_freqs(16)
    ref, ref_ctr = numpy_ref.subband(data, freqs, 64e-6, 4, subdm)
    got, ctr = kernels.subband(jnp.asarray(data), jnp.asarray(freqs), 64e-6, 4, subdm)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ctr), ref_ctr, rtol=1e-6)


@pytest.mark.parametrize("factor", [1, 2, 5])
def test_downsample_parity(factor):
    data = make_data(4, 103)
    ref = numpy_ref.downsample(data, factor)
    got = np.asarray(kernels.downsample(jnp.asarray(data), factor))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("padval", [0, "mean", "median", "wrap"])
@pytest.mark.parametrize("width", [1, 4, 7])
def test_smooth_parity(width, padval):
    data = make_data(4, 64)
    ref = numpy_ref.smooth(data, width, padval)
    got = np.asarray(kernels.smooth(jnp.asarray(data), width, padval))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("indep", [False, True])
def test_scaled_parity(indep):
    data = make_data()
    np.testing.assert_allclose(
        np.asarray(kernels.scaled(jnp.asarray(data), indep)),
        numpy_ref.scaled(data, indep),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(kernels.scaled2(jnp.asarray(data), indep)),
        numpy_ref.scaled2(data, indep),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize("maskval", ["median", "mean", "median-mid80", 7.0])
def test_masked_parity(maskval):
    data = make_data(8, 100)
    mask = RNG.rand(8, 100) > 0.8
    ref = numpy_ref.masked(data, mask, maskval)
    got = np.asarray(kernels.masked(jnp.asarray(data), jnp.asarray(mask), maskval))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def _maskval_rows(kind, T):
    rng = np.random.RandomState(1000 + T)
    x = (rng.randn(4, T) * 50).astype(np.float32)
    if kind == "2bit":  # what a 2-bit pointing widens to: heavy ties
        x = rng.randint(0, 4, size=(4, T)).astype(np.float32)
    elif kind == "constant":
        x[:] = 2.5
    elif kind == "signed-zeros":
        x = rng.randint(-1, 2, size=(4, T)).astype(np.float32)
        x[x == 0] = rng.choice(np.float32([-0.0, 0.0]), size=(x == 0).sum())
    elif kind == "inf":
        x[:, 0] = np.inf
        x[1::2, -1] = -np.inf  # T == 1: rows of -inf and of +inf
    elif kind == "nan":
        x[1, T // 2] = np.nan
    return x


@pytest.mark.parametrize("maskval", ["median", "median-mid80"])
@pytest.mark.parametrize(
    "kind", ["normal", "2bit", "constant", "signed-zeros", "inf", "nan"])
@pytest.mark.parametrize("T", [1, 2, 9, 10, 11, 1000, 4097])
def test_channel_maskvals_selects_the_sorted_middle(T, kind, maskval):
    """The fill value is selected, not sorted for, and keeps its bits:
    equal to the float32 median of the sorted, trimmed row for odd and
    even lengths, with and without a trim (n = 0 up to T = 4), under
    ties, infinities and signed zeros; a row holding a NaN reads NaN."""
    x = _maskval_rows(kind, T)
    n = int(np.round(0.1 * T)) if maskval == "median-mid80" else 0
    with np.errstate(invalid="ignore"):  # the mean of -inf and +inf
        want = np.median(np.sort(x, axis=-1)[:, n:T - n], axis=-1)
    assert want.dtype == np.float32
    want[np.isnan(x).any(axis=-1)] = np.nan
    got = np.asarray(kernels.channel_maskvals(jnp.asarray(x), maskval))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)
    if kind == "nan":
        assert np.isnan(got[1]) and not np.isnan(got[[0, 2, 3]]).any()


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bfloat16])
def test_channel_maskvals_other_dtypes_take_one_sort(dtype):
    """Any dtype but float32 sorts once and reads the middle of that."""
    import jax

    x = np.random.RandomState(3).randint(0, 50, size=(4, 10))
    fn = jax.jit(lambda d: kernels.channel_maskvals(d, "median-mid80"))
    arg = jnp.asarray(x, dtype)
    np.testing.assert_array_equal(
        np.asarray(fn(arg), np.float32), np.median(x, axis=-1))
    assert fn.lower(arg).as_text().count("stablehlo.sort") == 1
    f32 = jnp.asarray(x, jnp.float32)
    assert "stablehlo.sort" not in fn.lower(f32).as_text()


def test_zero_dm_parity():
    data = make_data()
    np.testing.assert_allclose(
        np.asarray(kernels.zero_dm(jnp.asarray(data))),
        numpy_ref.zero_dm(data),
        rtol=1e-5, atol=1e-5,
    )


def test_boxcar_snr_parity():
    ts = RNG.randn(512).astype(np.float32)
    ts[100:104] += 8.0
    widths = (1, 2, 4, 8)
    ref_snr, ref_idx = numpy_ref.boxcar_snr(ts, widths)
    snr, idx = kernels.boxcar_snr(jnp.asarray(ts), widths)
    np.testing.assert_allclose(np.asarray(snr), ref_snr, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(idx), ref_idx)


def test_dedispersed_timeseries_recovers_pulse():
    # inject a dispersed pulse; dedispersing at the true DM must align it
    C, T, dt, dm = 64, 2048, 64e-6, 30.0
    freqs = make_freqs(C, 1500.0, -2.0)
    data = RNG.randn(C, T).astype(np.float32) * 0.1
    bins = numpy_ref.bin_delays(dm, freqs, dt)
    t0 = 300
    for c in range(C):
        data[c, (t0 + bins[c]) % T] += 5.0
    ts = np.asarray(kernels.dedispersed_timeseries(jnp.asarray(data), jnp.asarray(bins)))
    assert ts.argmax() == t0
    ref_ts = numpy_ref.dedispersed_timeseries(data, bins)
    np.testing.assert_allclose(ts, ref_ts, rtol=1e-4, atol=1e-3)


class TestSpectra:
    def _spec(self, C=16, T=128):
        data = make_data(C, T)
        return data, Spectra(make_freqs(C), 64e-6, data)

    def test_constructor_honors_dm(self):
        # reference defect spectra.py:37 fixed: dm argument kept
        s = Spectra(make_freqs(4), 1e-3, make_data(4, 16), dm=12.5)
        assert s.dm == 12.5

    def test_dedisperse_roundtrip(self):
        data, s = self._spec()
        d = s.dedisperse(40.0, padval="rotate")
        assert d.dm == 40.0
        back = d.dedisperse(0.0, padval="rotate")
        np.testing.assert_allclose(back.to_numpy(), data, atol=1e-6)

    def test_dedisperse_trim(self):
        data, s = self._spec()
        d = s.dedisperse(100.0, trim=True)
        maxdel = int(numpy_ref.bin_delays(100.0, make_freqs(16), 64e-6).max())
        assert d.numspectra == 128 - maxdel

    def test_downsample_updates_dt(self):
        _, s = self._spec()
        d = s.downsample(4)
        assert d.dt == pytest.approx(4 * 64e-6)
        assert d.numspectra == 32

    def test_trim_negative_moves_starttime(self):
        _, s = self._spec()
        t = s.trim(-10)
        assert t.numspectra == 118
        assert t.starttime == pytest.approx(10 * 64e-6)

    def test_subband(self):
        data, s = self._spec()
        sb = s.subband(4, subdm=25.0)
        ref, ctr = numpy_ref.subband(data, make_freqs(16), 64e-6, 4, 25.0)
        np.testing.assert_allclose(sb.to_numpy(), ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(sb.freqs), ctr, rtol=1e-6)

    def test_pytree(self):
        import jax

        _, s = self._spec(4, 16)
        leaves, treedef = jax.tree_util.tree_flatten(s)
        s2 = jax.tree_util.tree_unflatten(treedef, leaves)
        np.testing.assert_array_equal(s2.to_numpy(), s.to_numpy())
        assert s2.dt == s.dt


def test_shift_channels_fourier_matches_gather():
    """The TPU fourier shift backend (round 5: the gather path measured
    ~70M elem/s on chip, BENCHNOTES) agrees with the bit-exact gather
    formulation to FFT f32 rounding for every padval mode, including
    negative shifts and fully-vacated rows (|s| >= T)."""
    from pypulsar_tpu.ops.kernels import shift_channels

    rng = np.random.RandomState(8)
    C, T = 16, 1000
    data = rng.randn(C, T).astype(np.float32)
    bins = np.array([0, 1, -1, 7, -7, 500, -500, 999, -999, 1000, -1000,
                     1500, -1500, 3, 250, -250], dtype=np.int32)
    for padval in (0, 5.0, "mean", "median"):
        a = np.asarray(shift_channels(data, jnp.asarray(bins), padval,
                                      backend="gather"))
        b = np.asarray(shift_channels(data, jnp.asarray(bins), padval,
                                      backend="fourier"))
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-4,
                                   err_msg=f"padval={padval}")
