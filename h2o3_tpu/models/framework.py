"""Model framework: Parameters / Model / ModelBuilder / Job lifecycle.

Reference: ``hex/Model.java`` (scoring + test-frame adaptation + metrics
hookup, Model.java:1764 score, 2077 BigScore), ``hex/ModelBuilder.java``
(lifecycle + validation + cross-validation, ModelBuilder.java:228,368-377,597),
``water/Job.java`` (cancellable progress handle in the DKV).

TPU-native: the lifecycle is the same shape — validate params, build, score,
metrics — but scoring is a jitted batch computation over sharded arrays
instead of a per-row MRTask, and CV fold models are independent jit programs
(the reference's parallel fold building, hex/CVModelBuilder.java:10).
"""

from __future__ import annotations

import sys
import time
import uuid
from dataclasses import dataclass, field as dataclass_field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from h2o3_tpu.frame.frame import ColType, Column, Frame
from h2o3_tpu.keyed import DKV
from h2o3_tpu.models import metrics as M
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.util import telemetry

#: fit accounting: wall seconds per algo (histogram — AutoML fans dozens of
#: fits through here) + outcome counter; the fit Span makes every timeline
#: event of a build (train blocks, mapreduce dispatches) share one trace_id
_FIT_SECONDS = telemetry.histogram(
    "model_fit_seconds", "model build wall seconds", labels=("algo",)
)
_FITS = telemetry.counter(
    "model_fit_total", "model builds", labels=("algo", "outcome")
)


@dataclass
class ModelParameters:
    """Common hyperparameters (reference: hex/Model.Parameters)."""

    response_column: Optional[str] = None
    ignored_columns: List[str] = dataclass_field(default_factory=list)
    weights_column: Optional[str] = None
    offset_column: Optional[str] = None
    fold_column: Optional[str] = None
    nfolds: int = 0
    fold_assignment: str = "auto"  # auto|random|modulo|stratified
    keep_cross_validation_predictions: bool = False
    seed: int = -1
    max_runtime_secs: float = 0.0
    stopping_rounds: int = 0
    stopping_metric: str = "auto"
    stopping_tolerance: float = 1e-3
    categorical_encoding: str = "auto"
    checkpoint: Optional[str] = None  # model key to continue training from

    def actual_seed(self) -> int:
        if self.seed is None or self.seed == -1:
            return int(time.time_ns() % (2**31))
        return int(self.seed)


class Job:
    """Cancellable, progress-reporting handle (water/Job.java)."""

    def __init__(self, description: str = "") -> None:
        self.key = DKV.make_key("job")
        self.description = description
        self.progress = 0.0
        #: live human-readable detail (e.g. distributed search streaming
        #: "3/12 models across 4 member(s)" via the search_progress RPC)
        self.progress_msg: Optional[str] = None
        self.status = "CREATED"
        self.start_time: Optional[float] = None
        self.end_time: Optional[float] = None
        self.exception: Optional[BaseException] = None
        self._cancel_requested = False
        DKV.put(self.key, self)

    def start(self) -> "Job":
        self.start_time = time.time()
        self.status = "RUNNING"
        return self

    def update(self, progress: float) -> None:
        self.progress = min(max(progress, 0.0), 1.0)

    def cancel(self) -> None:
        self._cancel_requested = True

    @property
    def stop_requested(self) -> bool:
        return self._cancel_requested

    def done(self) -> None:
        self.end_time = time.time()
        self.progress = 1.0
        self.status = "DONE" if not self._cancel_requested else "CANCELLED"

    def fail(self, e: BaseException) -> None:
        self.end_time = time.time()
        self.exception = e
        self.status = "FAILED"

    @property
    def run_time(self) -> float:
        end = self.end_time if self.end_time is not None else time.time()
        return (end - self.start_time) if self.start_time else 0.0


def prediction_frame(raw: np.ndarray, domain, threshold: float = 0.5) -> Frame:
    """Raw scores -> the canonical predictions frame (Model.score layout).

    domain None => not a classifier: 1-D raw becomes a 'predict' numeric
    column; 2-D raw (PCA projections, autoencoder reconstructions) becomes
    one numeric column per output. With a domain, binomial labels threshold
    ``p[:, 1]`` at ``threshold`` (training max-F1 by default), multinomial
    labels argmax; per-class columns are named p<level>.
    """
    if domain is None:
        if raw.ndim == 1:
            return Frame(
                [Column("predict", raw.astype(np.float64), ColType.NUM)])
        return Frame([
            Column(f"C{k + 1}", raw[:, k].astype(np.float64), ColType.NUM)
            for k in range(raw.shape[1])
        ])
    if raw.shape[1] == 2:
        labels = (raw[:, 1] >= threshold).astype(np.int32)
    else:
        labels = raw.argmax(axis=1).astype(np.int32)
    cols = [Column("predict", labels, ColType.CAT, list(domain))]
    for k, lv in enumerate(domain):
        cols.append(
            Column(f"p{lv}", raw[:, k].astype(np.float64), ColType.NUM))
    return Frame(cols)


class Model:
    """Trained model: predict + metrics (hex/Model.java).

    Subclasses implement ``_predict_raw(frame) -> np.ndarray``:
      regression      -> [N] predictions
      binomial        -> [N, 2] class probabilities
      multinomial     -> [N, K] class probabilities
    """

    algo_name: str = "model"

    def __init__(self, params: ModelParameters, data_info: DataInfo) -> None:
        self.key = DKV.make_key(self.algo_name)
        self.params = params
        self.data_info = data_info
        self.training_metrics: Optional[Any] = None
        self.validation_metrics: Optional[Any] = None
        self.cross_validation_metrics: Optional[Any] = None
        self.scoring_history: List[Dict[str, Any]] = []
        self.run_time: float = 0.0
        #: where the fit's wall went, by its own spans (:func:`fit_profile`)
        self.fit_profile: Dict[str, Dict[str, float]] = {}
        DKV.put(self.key, self)

    # -- category of the learning problem -----------------------------------
    @property
    def nclasses(self) -> int:
        dom = self.data_info.response_domain
        return len(dom) if dom else 1

    @property
    def is_classifier(self) -> bool:
        return self.nclasses > 1

    # -- scoring (Model.score, Model.java:1764) ------------------------------
    def _predict_raw(self, frame: Frame) -> np.ndarray:
        raise NotImplementedError

    def _apply_preprocessors(self, frame: Frame) -> Frame:
        """Models trained on a preprocessed frame (e.g. AutoML target
        encoding) carry their transformers in ``self.preprocessors`` so a
        RAW frame scores correctly (the reference embeds TE in the model
        pipeline; here the transform re-applies at score time). A frame
        already carrying the derived columns passes through untouched."""
        for pre in getattr(self, "preprocessors", None) or []:
            outs = [f"{name}_te" for name in getattr(pre, "encodings", {})]
            if outs and all(o in frame.names for o in outs):
                continue  # already transformed (e.g. the training frame)
            frame = pre.transform(frame)
        return frame

    def default_threshold(self) -> float:
        """Binomial label threshold: an explicit reset wins, else the
        training max-F1 (Model._output.defaultThreshold())."""
        override = getattr(self, "_threshold_override", None)
        if override is not None:
            return override
        return getattr(self.training_metrics, "max_f1_threshold", 0.5) or 0.5

    def reset_threshold(self, threshold: float) -> float:
        """Set the classification threshold used by predict; returns the
        previous effective threshold (Model.resetThreshold,
        rapids ``model.reset.threshold``)."""
        old = self.default_threshold()
        self._threshold_override = float(threshold)
        return old

    def predict(self, frame: Frame) -> Frame:
        """Predictions frame: 'predict' (+ per-class probability columns)."""
        frame = self._apply_preprocessors(frame)
        return self.prediction_from_raw(self._predict_raw(frame))

    def prediction_from_raw(self, raw: np.ndarray) -> Frame:
        """Raw scores -> the predictions frame (the second half of
        ``predict``; the serving coalescer computes raw once per batch and
        fans it out per caller through here)."""
        if not self.is_classifier:
            return prediction_frame(raw, None)
        return prediction_frame(raw, self.data_info.response_domain,
                                self.default_threshold())

    def predict_raw_batched(
        self, frames: Sequence[Frame]
    ) -> List[Tuple[np.ndarray, Frame]]:
        """One raw-score pass over several frames (the coalesced REST
        scoring entry).  Returns ``(raw, preprocessed_frame)`` per input,
        aligned.  Identical frames — same object, or equal (names, types,
        version) stamps, the devcache identity — score ONCE and share the
        result; distinct frames with one schema row-stack into a single
        ``_predict_raw`` dispatch and split back per caller.  Every
        ``_predict_raw`` scores row-wise (no cross-row coupling), so both
        paths are bit-identical to per-frame calls; anything unstackable
        falls back to one dispatch per distinct frame."""
        pres = [self._apply_preprocessors(f) for f in frames]
        uniq: List[Frame] = []
        which: List[int] = []
        seen: Dict[Any, int] = {}
        for f in pres:
            try:
                sig: Any = (tuple(f.names),
                            tuple(c.type for c in f.columns), f.version)
            except Exception:
                sig = id(f)
            i = seen.get(sig)
            if i is None:
                i = seen[sig] = len(uniq)
                uniq.append(f)
            which.append(i)
        if len(uniq) == 1:
            raws = [self._predict_raw(uniq[0])]
        else:
            head = uniq[0]
            same_schema = all(
                u.names == head.names
                and [c.type for c in u.columns]
                == [c.type for c in head.columns]
                for u in uniq[1:]
            )
            if same_schema:
                stacked = head
                for u in uniq[1:]:
                    stacked = stacked.rbind(u)
                raw_all = self._predict_raw(stacked)
                raws, off = [], 0
                for u in uniq:
                    raws.append(raw_all[off:off + u.nrows])
                    off += u.nrows
            else:
                raws = [self._predict_raw(u) for u in uniq]
        return [(raws[i], pres[k]) for k, i in enumerate(which)]

    def model_performance(self, frame: Frame) -> Any:
        """Score a frame and build the right ModelMetrics (Model.score + MM builders)."""
        with telemetry.Span("model_performance", rows=frame.nrows):
            frame = self._apply_preprocessors(frame)
            return self._metrics_from_raw(frame, self._predict_raw(frame))

    def _metrics_from_raw(self, frame: Frame, raw: np.ndarray) -> Any:
        """ModelMetrics from an already-computed raw score over an already-
        preprocessed frame — ``model_performance`` minus the scoring pass,
        so the batched REST path never scores the same frame twice."""
        from h2o3_tpu.models.data_info import response_vector

        with telemetry.Span("score_metrics", rows=frame.nrows):
            y = response_vector(self.data_info, frame)
            w = (
                frame.col(self.params.weights_column).numeric_view()
                if self.params.weights_column
                else None
            )
            return self._metrics(y, raw, w)

    def _metrics(self, y: np.ndarray, raw: np.ndarray, w) -> Any:
        """The ModelMetrics of this model's kind for a response, its raw
        scores and the rows' weights (or None)."""
        if not self.is_classifier:
            return M.regression_metrics(y, raw, weights=w)
        if self.nclasses == 2:
            return M.binomial_metrics(y, raw[:, 1], weights=w)
        return M.multinomial_metrics(
            y.astype(np.int64), raw, self.data_info.response_domain, weights=w
        )

    def pojo(self, lang: str = "c") -> str:
        """Standalone scoring source (hex/tree/TreeJCodeGen / water/codegen
        POJO export, /3/Models.java): C (compiles with any C99 toolchain)
        or Java (genmodel score0 shape). Tree models + GLM."""
        from h2o3_tpu.models.pojo import pojo_source

        return pojo_source(self, lang)

    def download_mojo(self, path: str) -> str:
        """Export as a portable MOJO zip (Model.getMojo, /3/Models .../mojo);
        scored offline by the numpy-only ``h2o3_tpu.genmodel`` package."""
        from h2o3_tpu.models.mojo_export import write_mojo

        return write_mojo(self, path)

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.key} metrics={self.training_metrics!r}>"


def fit_profile(train_span: telemetry.Span,
                counts: Tuple[str, ...] = ()) -> Dict[str, Dict[str, float]]:
    """Where a fit's wall went, from its own spans in the timeline ring:
    seconds (``s``) and count (``n``) of every kind of span under
    ``train_span``; a kind met under ``model_performance`` is keyed
    ``score/<kind>``, so the entry's ``tree_matrix`` and the scoring's stay
    apart; the fields named in ``counts`` (the builder's
    ``profile_counts``) are summed beside them where they are numbers and
    kept as the span states them where they are not (a tree block's
    ``hist_slots``).  Empty where the ring no longer holds the fit."""
    from h2o3_tpu.util import timeline

    spans = {e["span_id"]: e for e in timeline.snapshot(timeline.CAPACITY)
             if e.get("trace_id") == train_span.trace_id and "parent_id" in e}
    out: Dict[str, Dict[str, float]] = {}
    for e in spans.values():
        scoring, up = False, e
        while up is not None and up["span_id"] != train_span.span_id:
            scoring = scoring or up["kind"] == "model_performance"
            up = spans.get(up["parent_id"])
        if up is None or e["span_id"] == train_span.span_id:
            continue  # not under this fit (the enclosing request's spans)
        key = e["kind"]
        if scoring and key != "model_performance":
            key = "score/" + key
        slot = out.setdefault(key, {"s": 0.0, "n": 0})
        slot["s"] = round(slot["s"] + e["duration_ms"] / 1e3, 6)
        slot["n"] += 1
        for name in counts:
            if name not in e:
                continue
            if isinstance(e[name], (int, float)):
                slot[name] = slot.get(name, 0) + int(e[name])
            else:  # a plan, the same in every span of the kind: kept
                slot[name] = e[name]
    return out


def _profile_text(profile: Dict[str, Dict[str, float]]) -> str:
    """``tree_block 35.89s x4, score/apply_bins 9.52s, ...`` longest first."""
    return ", ".join(
        "%s %.2fs%s%s" % (k, v["s"], " x%d" % v["n"] if v["n"] > 1 else "",
                          "".join(" %s=%d" % (c, x) for c, x in v.items()
                                  if c not in ("s", "n") and isinstance(x, int)))
        for k, v in sorted(profile.items(), key=lambda kv: -kv[1]["s"]))


class ModelBuilder:
    """Train lifecycle (hex/ModelBuilder.java:368-377 trainModel).

    Subclasses set ``model_class`` and implement ``_fit(frame) -> Model``.
    ``train`` adds: parameter validation, the Job, cross-validation
    (ModelBuilder.java:597 computeCrossValidation), and main-model CV metrics
    from the aggregated holdout predictions.
    """

    algo_name: str = "builder"

    #: Common ModelParameters fields this builder honors beyond the
    #: framework-provided ones (CV, seed, response/ignored columns). Setting
    #: any other guarded field to a non-default value raises instead of being
    #: silently ignored — the reference validates every param in
    #: hex/ModelBuilder.init (VERDICT r2: accepted-and-ignored params were the
    #: worst user-facing behavior; this guard makes them structurally
    #: impossible).
    SUPPORTED_COMMON: frozenset = frozenset()

    #: fields of the fit's spans that are counts: ``fit_profile`` sums them
    #: beside a kind's seconds
    profile_counts: Tuple[str, ...] = ()

    #: guarded field -> its dataclass default
    _GUARDED_DEFAULTS = {
        "weights_column": None,
        "offset_column": None,
        "checkpoint": None,
        "stopping_rounds": 0,
        "max_runtime_secs": 0.0,
        "categorical_encoding": "auto",
    }

    def __init__(self, params: ModelParameters) -> None:
        self.params = params
        self.job: Optional[Job] = None

    # -- validation (ModelBuilder.init) --------------------------------------
    def _validate_params(self) -> None:
        """Frame-independent checks: the no-silent-param guard + CV combos.
        Frame-free builders (generic) run this directly."""
        p = self.params
        for name, default in self._GUARDED_DEFAULTS.items():
            val = getattr(p, name, default)
            if val != default and name not in self.SUPPORTED_COMMON:
                raise ValueError(
                    f"{self.algo_name} does not support {name!r} "
                    f"(got {val!r}); supported common params: "
                    f"{sorted(self.SUPPORTED_COMMON) or 'none'}"
                )
        if p.nfolds == 1:
            raise ValueError("nfolds must be 0 or >= 2")
        if p.nfolds and p.fold_column:
            raise ValueError("cannot use both nfolds and fold_column")

    def _validate(self, frame: Frame) -> None:
        self._validate_params()
        p = self.params
        if p.response_column and p.response_column not in frame.names:
            raise ValueError(f"response_column {p.response_column!r} not in frame")
        if p.weights_column and p.weights_column not in frame.names:
            raise ValueError(f"weights_column {p.weights_column!r} not in frame")

    def _fit(self, frame: Frame, valid: Optional[Frame] = None) -> Model:
        raise NotImplementedError

    def train(self, frame: Frame, valid: Optional[Frame] = None) -> Model:
        from h2o3_tpu.util.log import get_logger

        log = get_logger("train")
        self._validate(frame)
        self.job = Job(f"{self.algo_name} train").start()
        t0 = time.time()
        log.info(
            "%s train start: %d rows x %d cols, response=%r",
            self.algo_name, frame.nrows, frame.ncols,
            self.params.response_column,
        )
        # Lockable: the training frame(s) must not be deleted mid-build
        locked = [
            fr.key for fr in (frame, valid)
            if fr is not None and getattr(fr, "key", None)
        ]
        for k in locked:
            DKV.read_lock(k, self.job.key)
        # a failed build must not strand a half-constructed model in the
        # DKV (Lockable.delete on builder failure); keys registered
        # during _fit are scope-tracked and swept unless the build wins
        DKV.scope_enter()
        keep = [self.job.key]
        try:
            # so the fit's spans say which of them built or loaded a program
            # (a host-only fit does not import the backend for it)
            if "jax" in sys.modules:
                telemetry.install_jax_compile_listener()
            with telemetry.Span(
                "train", algo=self.algo_name, rows=frame.nrows
            ) as span:
                model = self._fit(frame, valid)
                if self.params.nfolds >= 2 or self.params.fold_column:
                    self._cross_validate(model, frame)
                model.run_time = time.time() - t0
                span.set(train_s=round(model.run_time, 3))
                iters = getattr(model, "iterations", None)
                if isinstance(iters, (int, float)):
                    span.set(iterations=int(iters))
            model.fit_profile = fit_profile(span, self.profile_counts)
            _FIT_SECONDS.observe(model.run_time, algo=self.algo_name)
            _FITS.inc(algo=self.algo_name, outcome="ok")
            self.job.done()
            keep = None  # success: everything the build registered lives
            # on a live multi-node cloud the finished model is homed onto
            # the serving ring (blob + replicas) so ANY member can score
            # it; best-effort — a failed homing leaves builder-local
            # serving intact (cluster/serving.py)
            from h2o3_tpu.cluster import active_cloud as _active_cloud

            if _active_cloud() is not None:
                from h2o3_tpu.cluster import serving as _serving

                _serving.home_model(model)
            # the phase split rides the line, so an untraced run that
            # stalls says where
            log.info(
                "%s train done in %.2fs -> %s [%s]", self.algo_name,
                model.run_time, model.key, _profile_text(model.fit_profile),
            )
            return model
        except BaseException as e:
            _FITS.inc(algo=self.algo_name, outcome="error")
            self.job.fail(e)
            log.error("%s train failed: %s: %s", self.algo_name, type(e).__name__, e)
            raise
        finally:
            if keep is None:
                DKV.scope_exit(keep=DKV.keys())  # keep all
            else:
                DKV.scope_exit(keep=keep)
            for k in locked:
                DKV.read_unlock(k, self.job.key)

    # -- cross-validation (ModelBuilder.computeCrossValidation) --------------
    def _cross_validate(self, main_model: Model, frame: Frame) -> None:
        from h2o3_tpu.models.data_info import response_vector

        p = self.params
        fold = fold_assignment(
            n=frame.nrows,
            nfolds=p.nfolds,
            scheme=p.fold_assignment,
            seed=p.actual_seed(),
            y=response_vector(main_model.data_info, frame) if p.fold_assignment == "stratified" else None,
            fold_column=frame.col(p.fold_column).numeric_view().astype(np.int64)
            if p.fold_column
            else None,
        )
        nfolds = int(fold.max()) + 1
        nclasses = main_model.nclasses
        holdout = (
            np.full(frame.nrows, np.nan)
            if nclasses == 1
            else np.full((frame.nrows, nclasses), np.nan)
        )
        cv_models = []
        for f in range(nfolds):
            tr = frame.rows(fold != f)
            te = frame.rows(fold == f)
            sub = type(self)(_clone_params_no_cv(p))
            m = sub._fit(tr)
            cv_models.append(m)
            holdout[fold == f] = m._predict_raw(te)
            self.job.update(0.5 + 0.5 * (f + 1) / nfolds)
        y = response_vector(main_model.data_info, frame)
        w = (
            frame.col(p.weights_column).numeric_view() if p.weights_column else None
        )
        if nclasses == 1:
            main_model.cross_validation_metrics = M.regression_metrics(y, holdout, weights=w)
        elif nclasses == 2:
            main_model.cross_validation_metrics = M.binomial_metrics(y, holdout[:, 1], weights=w)
        else:
            main_model.cross_validation_metrics = M.multinomial_metrics(
                y.astype(np.int64), holdout, main_model.data_info.response_domain, weights=w
            )
        main_model.cv_models = cv_models
        if p.keep_cross_validation_predictions:
            main_model.cv_holdout_predictions = holdout


def _clone_params_no_cv(p: ModelParameters) -> ModelParameters:
    import copy

    q = copy.deepcopy(p)
    q.nfolds = 0
    q.fold_column = None
    return q


def fold_assignment(
    n: int,
    nfolds: int,
    scheme: str = "auto",
    seed: int = 42,
    y: Optional[np.ndarray] = None,
    fold_column: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Row -> fold id (hex/FoldAssignment.java). auto==random; modulo is
    deterministic row%nfolds; stratified balances class frequencies per fold."""
    if fold_column is not None:
        vals = fold_column
        uniq = np.unique(vals)
        remap = {v: i for i, v in enumerate(uniq)}
        return np.array([remap[v] for v in vals], dtype=np.int64)
    if scheme in ("auto", "random"):
        rng = np.random.default_rng(seed)
        return rng.integers(0, nfolds, size=n)
    if scheme == "modulo":
        return np.arange(n) % nfolds
    if scheme == "stratified":
        assert y is not None, "stratified fold assignment needs the response"
        rng = np.random.default_rng(seed)
        fold = np.zeros(n, dtype=np.int64)
        for cls in np.unique(y[~np.isnan(y)]):
            idx = np.nonzero(y == cls)[0]
            perm = rng.permutation(len(idx))
            fold[idx[perm]] = np.arange(len(idx)) % nfolds
        return fold
    raise ValueError(f"unknown fold_assignment {scheme!r}")
