"""A matrix of more cells than the binning program holds at once is binned
a block of rows at a time by one program, and its absent entries counted."""
import numpy as np
import pytest

from xgboost_tpu.data import ellpack


@pytest.mark.parametrize("rows,limit,blocks", [
    (1003, 4000, 8),   # the last block starts early to be whole
    (1000, 4000, 7),   # 143 rows a block, 1 of the last dropped
    (1024, 4096, 7),
    (500, 4000, 1),    # under the limit: one call on the whole matrix
])
def test_row_blocks_bin_every_row_once_with_one_shape(monkeypatch, rows,
                                                      limit, blocks):
    import jax.numpy as jnp

    monkeypatch.setattr(ellpack, "_BIN_CELLS", limit)
    X = jnp.asarray(np.arange(rows * 7, dtype=np.float32).reshape(rows, 7))
    shapes = []

    def bin_rows(x):
        shapes.append(x.shape)
        return x.astype(jnp.int32)

    out = ellpack._in_row_blocks(bin_rows, X)
    assert out.shape == X.shape and bool((out == X.astype(jnp.int32)).all())
    assert len(shapes) == blocks and len(set(shapes)) == 1
    # a block holds a quarter of the limit, to within a row
    assert (shapes[0][0] - 1) * 7 < limit // 4 or blocks == 1


def test_the_cells_of_the_benchmark_that_were_there_are_binned_whole():
    for rows, columns in ((10_500_000, 28), (2_270_296, 136)):
        assert rows * columns <= ellpack._BIN_CELLS
    assert 946_997 * 968 > ellpack._BIN_CELLS


@pytest.mark.parametrize("absent", [0.0, 0.4, 1.0])
def test_count_missing_counts_the_logical_rows(absent):
    from xgboost_tpu.data.quantile import sketch_dense

    rng = np.random.default_rng(0)
    X = rng.normal(size=(1500, 5)).astype(np.float32)
    X[rng.random(X.shape) < absent] = np.nan
    page = ellpack.build_ellpack(X, sketch_dense(X, 16), row_align=1024)
    assert page.n_padded == 2048  # the padded rows hold the sentinel too
    assert ellpack.count_missing(page) == (X.size, int(np.isnan(X).sum()))
