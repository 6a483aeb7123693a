"""DMatrix family: user-facing data containers.

TPU-native re-design of the reference DMatrix (include/xgboost/data.h:549,
MetaInfo data.h:65, SimpleDMatrix src/data/simple_dmatrix.h:20, QuantileDMatrix
src/data/iterative_dmatrix.h:34).  The reference keeps CSR pages and converts
to Ellpack/GHist lazily per tree method; here the canonical compute format IS
the Ellpack page (a dense jax.Array of bin indices), built lazily on first
training touch or eagerly by QuantileDMatrix.  ``ref=`` sharing of cuts between
train and validation mirrors GetCutsFromRef (src/data/quantile_dmatrix.cc:19).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

import numpy as np

from ..telemetry import span
from .ellpack import (EllpackPage, build_ellpack, build_ellpack_csr,
                      count_missing)
from .quantile import HistogramCuts, sketch_csr, sketch_dense


@dataclasses.dataclass
class MetaInfo:
    """Labels and auxiliary per-row/per-feature metadata (reference: data.h:65-116)."""

    num_row: int = 0
    num_col: int = 0
    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    base_margin: Optional[np.ndarray] = None
    group_ptr: Optional[np.ndarray] = None  # ranking query groups (CSR ptr)
    label_lower_bound: Optional[np.ndarray] = None  # survival
    label_upper_bound: Optional[np.ndarray] = None
    feature_names: Optional[List[str]] = None
    feature_types: Optional[List[str]] = None
    feature_weights: Optional[np.ndarray] = None

    def validate(self) -> None:
        for name in ("label", "weight", "base_margin"):
            arr = getattr(self, name)
            if arr is not None and arr.shape[0] != self.num_row:
                raise ValueError(
                    f"{name} has {arr.shape[0]} rows, expected {self.num_row}"
                )
        if self.group_ptr is not None and self.group_ptr[-1] != self.num_row:
            raise ValueError("group sizes must sum to num_row")


def _load_uri(uri: str):
    """DMatrix::Load (data.h:610): 'path', 'path?format=libsvm|csv'.

    Parsing runs in the native C++ library (native/xtb_native.cc) with a
    Python fallback — the analogue of dmlc-core's text parsers."""
    from ..utils.native import parse_csv, parse_libsvm

    path, _, query = uri.partition("?")
    fmt = None
    for part in query.split("&"):
        if part.startswith("format="):
            fmt = part.split("=", 1)[1]
    if fmt is None:
        fmt = "csv" if path.endswith(".csv") else "libsvm"
    if fmt == "csv":
        arr = parse_csv(path)
        return ("dense", arr), None, None, None, None
    indptr, indices, values, labels, qids, n_col = parse_libsvm(path)
    return (("csr", (indptr, indices, values, (len(indptr) - 1, n_col))),
            None, None, labels, qids)


def _is_jax_array(data: Any) -> bool:
    return type(data).__module__.split(".")[0] in ("jax", "jaxlib") and hasattr(
        data, "devices"
    )


def _normalize_dense(arr, missing: float, xp, feature_types=None):
    """1-D promotion + custom-missing -> NaN, shared by the host (xp=numpy)
    and device (xp=jax.numpy) ingest paths so their semantics cannot drift.

    ``feature_types``: when given (columnar adapters), the sentinel applies
    to NUMERIC columns only — categorical columns already hold dictionary
    CODES whose values are unrelated to the user's sentinel (a sentinel of
    0.0 must not wipe out category 0)."""
    if arr.ndim == 1:
        arr = arr[:, None]
    missing_is_nan = missing is None or (
        isinstance(missing, (float, np.floating)) and np.isnan(missing))
    if not missing_is_nan:
        hit = arr == missing
        if feature_types is not None:
            num_col = np.asarray([t != "c" for t in feature_types], bool)
            hit = hit & num_col[None, :]
        arr = xp.where(hit, xp.nan, arr)
    return arr


def categories_by_name(cat_categories: Optional[dict],
                       feature_names: Optional[Sequence[str]],
                       ) -> Optional[Dict[str, list]]:
    """Render a ``{feature index -> category values}`` mapping with feature
    NAMES as keys (index string when unnamed) — the single implementation
    behind every ``get_categories`` surface (DMatrix, Booster,
    InferenceSnapshot; reference: src/data/cat_container.h)."""
    if not cat_categories:
        return None
    names = feature_names
    return {
        (names[fi] if names and fi < len(names) else str(fi)): list(vals)
        for fi, vals in sorted(cat_categories.items())
    }


def recode_dense(X: np.ndarray, train_cats: Optional[dict],
                 data_cats: Optional[dict]) -> np.ndarray:
    """Remap categorical codes in a dense matrix from ``data_cats`` (the
    frame the matrix was built from) onto ``train_cats`` (the TRAINING
    frame's category->code mapping; reference: encoder/ordinal.h Recode).
    Returns ``X`` untouched when the orderings already agree; raises on a
    category never seen in training.  Shared by Booster prediction and the
    serving snapshot so both route codes through the same split sets."""
    if not train_cats or not data_cats or train_cats == {
            int(k): list(v) for k, v in data_cats.items()}:
        return X
    X = np.array(X, copy=True)
    for f, train_vals in train_cats.items():
        new_vals = data_cats.get(f)
        if new_vals is None or list(new_vals) == list(train_vals):
            continue
        lookup = {v: i for i, v in enumerate(train_vals)}
        codes = X[:, f]
        remapped = np.full_like(codes, np.nan)
        for new_code, v in enumerate(new_vals):
            hit = codes == new_code
            if v in lookup:
                remapped[hit] = lookup[v]
            elif hit.any():
                raise ValueError(
                    f"feature {f} has category {v!r} not seen in "
                    "training (encoder recode)")
        X[:, f] = remapped
    return X


def _to_numpy_2d(data: Any, missing: float = np.nan):
    """Dispatch user input -> (dense ndarray | csr triple, feature names/types).

    Mirrors the adapter dispatch of the reference (src/data/adapter.h,
    python-package/xgboost/data.py): numpy, pandas, scipy CSR/CSC, list.
    Device arrays never pass through here — DMatrix keeps jax.Array input
    on device (the CudfAdapter/CupyAdapter role, src/data/device_adapter.cuh).
    """
    feature_names = None
    feature_types = None
    # torch / other dlpack producers: zero-copy host view (reference:
    # src/data/array_interface.h dlpack ingestion).  Zero-copy contract:
    # the caller must not mutate the buffer before training first touches
    # this DMatrix (binning is lazy for plain DMatrix).
    if not isinstance(data, np.ndarray) and hasattr(data, "__dlpack__"):
        try:
            data = np.from_dlpack(data)
        except (TypeError, RuntimeError, BufferError):
            pass  # fall through to np.asarray
    # pyarrow Table / RecordBatch (columnar adapter; reference:
    # ColumnarAdapter src/data/adapter.h:437 + data.py _from_arrow)
    from .arrow import arrow_to_columnar, is_arrow

    if is_arrow(data):
        return arrow_to_columnar(data, missing, _normalize_dense)
    # polars (columnar adapter; reference: ColumnarAdapter src/data/adapter.h
    # + python-package data.py _from_polars)
    if type(data).__module__.split(".")[0] == "polars":
        import polars as pl

        feature_names = list(data.columns)
        feature_types = []
        cols = []
        cat_categories = {}
        for fi, c in enumerate(data.columns):
            s = data[c]
            if s.dtype in (pl.Categorical, pl.Enum):
                cat_categories[fi] = [str(v) for v in
                                      s.cat.get_categories().to_list()]
                codes = s.to_physical().cast(pl.Float32).to_numpy().copy()
                cols.append(codes)
                feature_types.append("c")
            else:
                cols.append(s.cast(pl.Float32).to_numpy().copy())
                feature_types.append("q")
        arr = (np.stack(cols, axis=1) if cols
               else np.zeros((len(data), 0), np.float32))
        return (("dense",
                 _normalize_dense(arr, missing, np, feature_types),
                 cat_categories),
                feature_names, feature_types)
    # pandas
    if hasattr(data, "iloc") and hasattr(data, "columns"):
        feature_names = [str(c) for c in data.columns]
        feature_types = []
        cols = []
        cat_categories = {}
        for fi, c in enumerate(data.columns):
            col = data[c]
            if str(col.dtype) == "category":
                codes = col.cat.codes.to_numpy().astype(np.float32)
                codes[codes < 0] = np.nan  # pandas encodes NaN as -1
                cols.append(codes)
                feature_types.append("c")
                # category VALUES, for train->inference recode
                # (reference: src/encoder/ordinal.h Recode)
                cat_categories[fi] = [
                    v.item() if hasattr(v, "item") else v
                    for v in col.cat.categories.tolist()]
            else:
                cols.append(col.to_numpy().astype(np.float32))
                feature_types.append("q" if col.dtype.kind == "f" else "int")
        arr = np.stack(cols, axis=1) if cols else np.zeros((len(data), 0), np.float32)
        return (("dense",
                 _normalize_dense(arr, missing, np, feature_types),
                 cat_categories),
                feature_names, feature_types)
    # scipy sparse
    if hasattr(data, "tocsr"):
        csr = data.tocsr()
        return ("csr", (np.asarray(csr.indptr), np.asarray(csr.indices),
                        np.asarray(csr.data, dtype=np.float32), csr.shape)), None, None
    arr = _normalize_dense(np.asarray(data, dtype=np.float32), missing, np)
    return ("dense", arr), feature_names, feature_types


class DMatrix:
    """In-memory data matrix (reference: core.py:666 DMatrix, data.h:549).

    Holds raw host data + MetaInfo; binning to an EllpackPage happens lazily at
    training time (``ensure_ellpack``) or eagerly for QuantileDMatrix.
    """

    def __init__(
        self,
        data: Any,
        label: Any = None,
        *,
        weight: Any = None,
        base_margin: Any = None,
        missing: float = np.nan,
        feature_names: Optional[Sequence[str]] = None,
        feature_types: Optional[Sequence[str]] = None,
        group: Any = None,
        qid: Any = None,
        label_lower_bound: Any = None,
        label_upper_bound: Any = None,
        feature_weights: Any = None,
        nthread: Optional[int] = None,
        enable_categorical: bool = False,
        silent: bool = False,
    ) -> None:
        _prev_nthread = None
        if nthread is not None:
            # pool width scoped to this construction (the reference's
            # DMatrix nthread semantics); restored in the finally below —
            # results are bitwise-neutral either way
            from ..utils import native

            _prev_nthread = native.get_nthread()
            native.set_nthread(int(nthread))
        try:
            self._init_ingest(data, label, weight, base_margin, missing,
                              feature_names, feature_types, group, qid,
                              label_lower_bound, label_upper_bound,
                              feature_weights, enable_categorical)
        finally:
            if _prev_nthread is not None:
                from ..utils import native

                native.set_nthread(_prev_nthread)

    def _init_ingest(self, data, label, weight, base_margin, missing,
                     feature_names, feature_types, group, qid,
                     label_lower_bound, label_upper_bound, feature_weights,
                     enable_categorical) -> None:
        auto_label = auto_qid = None
        self.cat_categories = None  # {feature idx -> category values} (pandas)
        self._jax_X = None  # device-resident input (zero-copy jax.Array ingest)
        if isinstance(data, (str, os.PathLike)):
            (kind, payload), auto_names, auto_types, auto_label, auto_qid = _load_uri(
                os.fspath(data))
        elif _is_jax_array(data):
            # zero-copy device ingest: keep the array on device; host numpy is
            # materialized lazily only if a host path (raw predict, slice)
            # needs it (reference: device adapters skip the host round-trip,
            # src/data/device_adapter.cuh:67)
            import jax.numpy as jnp

            self._jax_X = _normalize_dense(
                jnp.asarray(data, dtype=jnp.float32), missing, jnp)
            kind, payload, auto_names, auto_types = "dense", None, None, None
        else:
            (kind, *rest), auto_names, auto_types = _to_numpy_2d(data, missing)
            payload = rest[0]
            if len(rest) > 1 and rest[1]:
                self.cat_categories = rest[1]
        self._kind = kind
        if kind == "dense":
            self._dense: Optional[np.ndarray] = payload
            self._csr = None
            num_row, num_col = (payload.shape if payload is not None
                                else self._jax_X.shape)
        else:
            self._dense = None
            self._csr = payload
            num_row, num_col = payload[3]
        self.info = MetaInfo(num_row=num_row, num_col=num_col)
        if label is None and auto_label is not None:
            self.set_label(auto_label)  # labels embedded in the data file
        if qid is None and auto_qid is not None:
            self.set_qid(auto_qid)
        if label is not None:
            self.set_label(label)
        if weight is not None:
            self.set_weight(weight)
        if base_margin is not None:
            self.set_base_margin(base_margin)
        if group is not None:
            self.set_group(group)
        if qid is not None:
            self.set_qid(qid)
        if label_lower_bound is not None:
            self.info.label_lower_bound = np.asarray(label_lower_bound, np.float32)
        if label_upper_bound is not None:
            self.info.label_upper_bound = np.asarray(label_upper_bound, np.float32)
        if feature_weights is not None:
            self.info.feature_weights = np.asarray(feature_weights, np.float32)
        self.info.feature_names = list(feature_names) if feature_names else auto_names
        self.info.feature_types = list(feature_types) if feature_types else auto_types
        self.info.validate()
        self._ellpack: Optional[EllpackPage] = None
        self._max_bin_built: Optional[int] = None

    # ---- setters (reference: core.py set_info family) ----
    def set_label(self, label: Any) -> None:
        arr = np.asarray(label, dtype=np.float32)
        if arr.shape[0] != self.num_row():
            raise ValueError(
                f"label has {arr.shape[0]} entries but data has {self.num_row()} rows"
            )
        self.info.label = arr.reshape(self.num_row(), -1)
        if self.info.label.shape[1] == 1:
            self.info.label = self.info.label[:, 0]

    def set_weight(self, weight: Any) -> None:
        self.info.weight = np.asarray(weight, dtype=np.float32).reshape(-1)

    def set_base_margin(self, margin: Any) -> None:
        self.info.base_margin = np.asarray(margin, dtype=np.float32)

    def set_group(self, group: Any) -> None:
        g = np.asarray(group, dtype=np.int64)
        self.info.group_ptr = np.concatenate([[0], np.cumsum(g)]).astype(np.int64)
        self._bump_group_version()

    def set_qid(self, qid: Any) -> None:
        q = np.asarray(qid)
        if len(q) == 0:
            return
        change = np.nonzero(np.diff(q) != 0)[0] + 1
        self.info.group_ptr = np.concatenate([[0], change, [len(q)]]).astype(np.int64)
        self._bump_group_version()

    def _bump_group_version(self) -> None:
        """Monotone counter so Booster caches keyed on the group layout
        cannot alias after allocator address reuse."""
        self.group_version = getattr(self, "group_version", 0) + 1

    # ---- shape ----
    def num_row(self) -> int:
        return self.info.num_row

    def num_col(self) -> int:
        return self.info.num_col

    def get_label(self) -> np.ndarray:
        return self.info.label if self.info.label is not None else np.zeros(self.num_row(), np.float32)

    def get_weight(self) -> Optional[np.ndarray]:
        return self.info.weight

    @property
    def feature_names(self):
        return self.info.feature_names

    @property
    def feature_types(self):
        return self.info.feature_types

    # ---- raw views for prediction ----
    def get_categories(self) -> Optional[dict]:
        """Category values per categorical feature, keyed by feature name (or
        index when unnamed), as captured from the input frame (pandas/polars/
        arrow dictionary columns).  None for purely numeric inputs
        (reference: ``XGDMatrixGetCategories``, src/data/cat_container.h)."""
        return categories_by_name(self.cat_categories,
                                  self.info.feature_names)

    def host_dense(self) -> np.ndarray:
        """Dense f32 view with NaN missing (prediction walks raw values)."""
        if self._dense is not None:
            return self._dense
        if self._jax_X is not None:  # lazy device -> host materialization
            self._dense = np.asarray(self._jax_X)
            return self._dense
        return self.host_dense_rows(0, self.num_row())

    def host_dense_rows(self, lo: int, hi: int) -> np.ndarray:
        """Densify only rows [lo, hi) — the bounded-memory window used by the
        streamed predictor (reference: gpu_predictor.cu:43-90 splits a
        SparsePage loader from the dense loader for the same reason)."""
        if self._dense is not None or self._jax_X is not None:
            return self.host_dense()[lo:hi]
        indptr, indices, values, (R, F) = self._csr
        hi = min(hi, R)
        out = np.full((hi - lo, F), np.nan, dtype=np.float32)
        a, b = indptr[lo], indptr[hi]
        row_of = np.repeat(np.arange(lo, hi), np.diff(indptr[lo : hi + 1])) - lo
        out[row_of, indices[a:b]] = values[a:b]
        return out

    def _device_dense(self):
        """Device f32 view of dense data, uploaded at most once — the sketch
        and the Ellpack build share it instead of each shipping X over the
        host->device link."""
        if self._jax_X is None:
            import jax.numpy as jnp

            # dispatch and host staging only: the copy itself ends inside
            # whoever first waits on the array (the sketch)
            with span("dmatrix.upload"):
                self._jax_X = jnp.asarray(self._dense, dtype=jnp.float32)
        return self._jax_X

    def cat_mask(self) -> Optional[np.ndarray]:
        """(F,) bool — which features are categorical ('c' feature type)."""
        ft = self.info.feature_types
        if not ft or "c" not in ft:
            return None
        return np.asarray([t == "c" for t in ft], dtype=bool)

    # ---- binning ----
    def ensure_ellpack(self, max_bin: int = 256, sketch_weights: Optional[np.ndarray] = None,
                       ref: Optional["DMatrix"] = None,
                       distributed: bool = False,
                       row_align: int = 1024) -> EllpackPage:
        if (self._ellpack is not None and self._max_bin_built == max_bin
                and self._ellpack.n_padded % row_align == 0):
            return self._ellpack
        with span("dmatrix.build"):
            self._ellpack = self._build_ellpack(
                max_bin, sketch_weights, ref, distributed, row_align)
        self._max_bin_built = max_bin
        return self._ellpack

    def _build_ellpack(self, max_bin: int, sketch_weights, ref, distributed,
                       row_align: int) -> EllpackPage:
        """Cuts (reused, borrowed or sketched), then the binned page."""
        if self._ellpack is not None and self._max_bin_built == max_bin:
            # alignment-only rebuild (n_devices changed): reuse the built
            # cuts — re-sketching would waste the work and, distributed, a
            # rank whose padding already divides row_align would take the
            # cache hit above while its peers re-enter the sketch
            # collectives alone (desync)
            cuts = self._ellpack.cuts
        elif ref is not None and ref._ellpack is not None:
            cuts = ref._ellpack.cuts  # GetCutsFromRef (quantile_dmatrix.cc:19)
        elif distributed and self._kind == "dense":
            # every process holds a row shard: merge the per-shard quantile
            # summaries into shared cuts (quantile.cc:397 AllreduceV analogue)
            from .quantile import sketch_distributed

            with span("dmatrix.sketch"):
                cuts = sketch_distributed(self.host_dense(), max_bin,
                                          weights=sketch_weights,
                                          cat_mask=self.cat_mask())
        elif self._kind == "dense":
            # weighted / categorical sketches run on host — feed them the
            # host array when we already have one rather than bouncing the
            # device upload back down
            cm = self.cat_mask()
            host_sketch = sketch_weights is not None or (
                cm is not None and cm.any())
            # host sketches get the cached host copy (one D2H transfer, reused
            # by later host paths) instead of bouncing the device array down
            sk_X = self.host_dense() if host_sketch else self._device_dense()
            # ends at the sketch's own np.asarray of the grid, so it is
            # drained, and holds what is left of the upload
            with span("dmatrix.sketch"):
                cuts = sketch_dense(sk_X, max_bin, weights=sketch_weights,
                                    cat_mask=cm)
        else:
            indptr, indices, values, (R, F) = self._csr
            with span("dmatrix.sketch"):
                cuts = sketch_csr(indptr, indices, values, F, max_bin,
                                  weights=sketch_weights,
                                  cat_mask=self.cat_mask(),
                                  distributed=distributed)
        # trace, compile-or-load and run of the binning program plus the pad,
        # drained: the count of the page's absent entries waits for the page
        with span("dmatrix.bin") as binning:
            if self._kind == "dense":
                ellpack = build_ellpack(self._device_dense(), cuts,
                                        row_align=row_align)
                if self._dense is not None:
                    self._jax_X = None  # binned; drop the duplicate device copy
            else:
                indptr, indices, values, (R, F) = self._csr
                ellpack = build_ellpack_csr(indptr, indices, values, F, cuts,
                                            row_align=row_align)
            cells, missing = count_missing(ellpack)
            # the rows of the one-hot a chunk of the page is multiplied as,
            # each column as tall as its tier (F*B where there is but one)
            from ..ops.histogram import onehot_rows, tier_widths

            shape = (ellpack.tiers, ellpack.bin_width, ellpack.n_features)
            binning.args.update({
                "bins.cells": cells, "bins.missing": missing,
                "bins.onehot_rows": onehot_rows(*shape),
                "bins.tiers": ",".join(f"{w}:{n}"
                                       for w, n in tier_widths(*shape))})
        return ellpack

    def slice(self, rindex: Sequence[int]) -> "DMatrix":
        """Row slice (reference: XGDMatrixSliceDMatrix) — used by cv()."""
        idx = np.asarray(rindex, dtype=np.int64)
        if self._kind == "dense":
            out = DMatrix(self.host_dense()[idx])
        else:
            import scipy.sparse as sp

            indptr, indices, values, shape = self._csr
            csr = sp.csr_matrix((values, indices, indptr), shape=shape)[idx]
            out = DMatrix(csr)
        info = self.info
        if info.label is not None:
            out.info.label = info.label[idx]
        if info.weight is not None:
            out.info.weight = info.weight[idx]
        if info.base_margin is not None:
            out.info.base_margin = info.base_margin[idx]
        if info.label_lower_bound is not None:
            out.info.label_lower_bound = info.label_lower_bound[idx]
        if info.label_upper_bound is not None:
            out.info.label_upper_bound = info.label_upper_bound[idx]
        if info.group_ptr is not None:
            # re-derive query groups for the selected rows (qid per row -> regroup)
            qid = np.repeat(np.arange(len(info.group_ptr) - 1), np.diff(info.group_ptr))
            out.set_qid(qid[idx])
        out.info.feature_weights = info.feature_weights
        out.info.feature_names = info.feature_names
        out.info.feature_types = info.feature_types
        return out


class QuantileDMatrix(DMatrix):
    """Eagerly-binned DMatrix (reference: core.py:1434, iterative_dmatrix.h:34).

    Sketches and bins at construction; ``ref=`` reuses the training cuts so
    validation data lands in identical bins.
    """

    def __init__(self, data: Any, label: Any = None, *, max_bin: int = 256,
                 ref: Optional[DMatrix] = None, **kwargs: Any) -> None:
        super().__init__(data, label, **kwargs)
        self.max_bin = max_bin
        self.ensure_ellpack(max_bin=max_bin, ref=ref)
