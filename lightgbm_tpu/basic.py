"""User-facing Dataset and Booster.

API-compatible re-implementation of the reference Python package's core
(reference: python-package/lightgbm/basic.py — Dataset at :909 with lazy
construction `_lazy_init` :1052, Booster at :1930 with update :2315,
predict :2816, save/load :2632-2760, refit :2873). There is no ctypes/C
ABI boundary here: the "C side" is the JAX/device engine in
lightgbm_tpu.boosting / treelearner, so Dataset wraps BinnedDataset and
Booster wraps the GBDT driver directly.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from . import obs
from .compile import get_manager
from .config import Config
from .io.dataset import BinnedDataset, _is_sparse
from .utils import log
from .utils.log import LightGBMError


def _to_2d_numpy(data):
    if hasattr(data, "values") and hasattr(data, "dtypes"):  # DataFrame
        return _pandas_to_numpy(data)
    if _is_sparse(data):  # consumed column-wise without densifying
        return data
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.dtype == object:
        arr = arr.astype(np.float64)
    return arr


def _pandas_to_numpy(df) -> np.ndarray:
    import pandas as pd
    out = np.empty(df.shape, dtype=np.float64)
    for i, col in enumerate(df.columns):
        s = df[col]
        if isinstance(s.dtype, pd.CategoricalDtype):
            out[:, i] = s.cat.codes.astype(np.float64)
            out[out[:, i] < 0, i] = np.nan
        else:
            out[:, i] = pd.to_numeric(s, errors="coerce").astype(np.float64)
    return out


def _label_from_pandas(label):
    if hasattr(label, "values"):
        return np.asarray(label.values, dtype=np.float64).reshape(-1)
    return None if label is None else np.asarray(label, dtype=np.float64).reshape(-1)


class Dataset:
    """Training data container (reference basic.py:909)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None, silent=False,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True) -> None:
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = copy.deepcopy(params) if params else {}
        self.free_raw_data = free_raw_data
        self._handle: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None
        self.pandas_categorical = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        """Lazy construction (reference basic.py:1274)."""
        if self._handle is not None:
            return self
        if self.used_indices is not None and hasattr(self, "_subset_parent"):
            return self._construct_subset()
        if self.reference is not None:
            ref = self.reference.construct()
        else:
            ref = None
        if isinstance(self.data, str):
            self._construct_from_file(self.data, ref)
            return self
        mat = _to_2d_numpy(self.data)
        if self.used_indices is not None:
            mat = mat.tocsr()[self.used_indices] if _is_sparse(mat) \
                else mat[self.used_indices]
        cfg = Config.from_params(self.params)
        feature_names = self._resolve_feature_names(mat.shape[1])
        cat = self._resolve_categorical(feature_names)
        label = _label_from_pandas(self.label)
        weight = None if self.weight is None else np.asarray(self.weight).reshape(-1)
        group = None if self.group is None else np.asarray(self.group).reshape(-1)
        init_score = None if self.init_score is None else np.asarray(self.init_score)
        self._handle = BinnedDataset.from_matrix(
            mat, cfg, label=label, weight=weight, group=group,
            init_score=init_score, feature_names=feature_names,
            categorical_feature=cat,
            reference=None if ref is None else ref._handle)
        if self.free_raw_data:
            self.data = None
        return self

    def _construct_from_file(self, path: str, ref) -> None:
        if path.endswith(".bin"):
            self._handle = BinnedDataset.load_binary(path)
            return
        from .io.text_loader import load_text_file
        cfg = Config.from_params(self.params)
        if ref is not None and cfg.initscore_filename:
            # the initscore_filename override names the TRAINING init
            # file; validation sets keep the <data>.init sidecar
            # convention (reference metadata.cpp LoadInitialScore)
            import dataclasses
            cfg = dataclasses.replace(cfg, initscore_filename="")
        mat, label, weight, group, init_score = load_text_file(path, cfg)
        feature_names = [f"Column_{i}" for i in range(mat.shape[1])]
        cat = self._resolve_categorical(feature_names)
        self._handle = BinnedDataset.from_matrix(
            mat, cfg, label=label, weight=weight, group=group,
            init_score=init_score,
            feature_names=feature_names, categorical_feature=cat,
            reference=None if ref is None else ref._handle)

    def _resolve_feature_names(self, ncol: int) -> List[str]:
        if isinstance(self.feature_name, list):
            return list(self.feature_name)
        if self.feature_name == "auto" and hasattr(self.data, "columns"):
            return [str(c) for c in self.data.columns]
        return [f"Column_{i}" for i in range(ncol)]

    def _resolve_categorical(self, feature_names: List[str]):
        cat = self.categorical_feature
        if cat == "auto" or cat is None:
            if hasattr(self.data, "dtypes"):
                import pandas as pd
                return [i for i, c in enumerate(self.data.columns)
                        if isinstance(self.data.dtypes.iloc[i], pd.CategoricalDtype)]
            return None
        out = []
        for c in cat:
            if isinstance(c, str):
                if c in feature_names:
                    out.append(feature_names.index(c))
            else:
                out.append(int(c))
        return out

    # ------------------------------------------------------------------
    @property
    def handle(self) -> Optional[BinnedDataset]:
        return self._handle

    def num_data(self) -> int:
        self.construct()
        return self._handle.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._handle.num_total_features

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._handle.feature_names)

    def get_label(self):
        if self._handle is not None and self._handle.metadata.label is not None:
            return np.asarray(self._handle.metadata.label)
        return _label_from_pandas(self.label)

    def get_weight(self):
        if self._handle is not None and self._handle.metadata.weights is not None:
            return np.asarray(self._handle.metadata.weights)
        return self.weight

    def get_group(self):
        if self._handle is not None and self._handle.metadata.query_boundaries is not None:
            return np.diff(self._handle.metadata.query_boundaries)
        return self.group

    def get_init_score(self):
        return self.init_score

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._handle is not None:
            self._handle.metadata.set_label(_label_from_pandas(label))
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._handle is not None:
            self._handle.metadata.set_weights(
                None if weight is None else np.asarray(weight).reshape(-1))
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._handle is not None:
            self._handle.metadata.set_query(
                None if group is None else np.asarray(group).reshape(-1))
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(
                None if init_score is None else np.asarray(init_score))
        return self

    def set_field(self, field_name: str, data) -> "Dataset":
        return {"label": self.set_label, "weight": self.set_weight,
                "group": self.set_group,
                "init_score": self.set_init_score}[field_name](data)

    def get_field(self, field_name: str):
        return {"label": self.get_label, "weight": self.get_weight,
                "group": self.get_group,
                "init_score": self.get_init_score}[field_name]()

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent=False,
                     params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, silent=silent,
                       params=params or self.params,
                       free_raw_data=self.free_raw_data)

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        """Row subset sharing this dataset's bin mappers (reference
        basic.py Dataset.subset / LGBM_DatasetGetSubset)."""
        if self.data is None and self._handle is None:
            raise LightGBMError("Cannot subset a freed dataset")
        ds = Dataset(self.data, label=self.label, reference=self,
                     weight=self.weight, group=self.group,
                     init_score=self.init_score,
                     feature_name=self.feature_name,
                     categorical_feature=self.categorical_feature,
                     params=params or self.params,
                     free_raw_data=False)
        ds.used_indices = np.asarray(sorted(used_indices), dtype=np.int64)
        ds._subset_parent = self
        return ds

    def _construct_subset(self) -> "Dataset":
        """Construct a subset using the parent's binned codes directly."""
        parent = self._subset_parent.construct()._handle
        idx = self.used_indices
        h = BinnedDataset()
        h.num_data = len(idx)
        h.num_total_features = parent.num_total_features
        h.bins = parent.bins[idx]
        h.bin_mappers = parent.bin_mappers
        h.real_feature_index = parent.real_feature_index
        h.inner_feature_index = parent.inner_feature_index
        h.feature_names = parent.feature_names
        h.max_bin = parent.max_bin
        h.bundles = parent.bundles
        from .io.dataset import Metadata
        h.metadata = Metadata(len(idx))
        if parent.metadata.label is not None:
            h.metadata.label = parent.metadata.label[idx]
        if parent.metadata.weights is not None:
            h.metadata.weights = parent.metadata.weights[idx]
        if self.group is not None:
            h.metadata.set_query(np.asarray(self.group))
        if parent.metadata.init_score is not None:
            isc = parent.metadata.init_score.reshape(-1, parent.num_data)
            h.metadata.init_score = isc[:, idx].reshape(-1)
        self._handle = h
        return self

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        self._handle.save_binary(filename)
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """reference Dataset::AddFeaturesFrom (dataset.cpp:1465)."""
        self.construct()
        other.construct()
        a, b = self._handle, other._handle
        if a.num_data != b.num_data:
            raise LightGBMError("Cannot add features from a different-size dataset")
        abins, bbins = a.feature_bins(), b.feature_bins()
        a.bundles = None
        a.bins = np.concatenate(
            [abins, bbins.astype(abins.dtype, copy=False)], axis=1) \
            if abins.dtype == bbins.dtype else np.concatenate(
                [abins.astype(np.uint16), bbins.astype(np.uint16)], axis=1)
        a.bin_mappers = list(a.bin_mappers) + list(b.bin_mappers)
        offset = a.num_total_features
        a.real_feature_index = list(a.real_feature_index) + \
            [offset + f for f in b.real_feature_index]
        a.num_total_features += b.num_total_features
        a.inner_feature_index = {f: i for i, f in enumerate(a.real_feature_index)}
        a.feature_names = list(a.feature_names) + list(b.feature_names)
        a._device_bins = None
        return self


# ---------------------------------------------------------------------------


class Booster:
    """Gradient-boosting model handle (reference basic.py:1930)."""

    _setup_reported = False     # the one `set-up:` line of this booster

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False) -> None:
        self.params = copy.deepcopy(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_set: Optional[Dataset] = None
        self.name_valid_sets: List[str] = []
        self._network_initialized = False

        from .boosting.gbdt import create_boosting
        from .objective.functions import create_objective
        from .metric.metrics import create_metric

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError(f"Training data should be Dataset instance, "
                                f"met {type(train_set).__name__}")
            cfg = Config.from_params(self.params)
            if train_set._handle is None:
                # dataset-level params given at train() time shape the
                # construction (max_bin, enable_bundle, ...) — reference
                # Dataset._update_params semantics: later params win
                train_set.params = {**(train_set.params or {}), **self.params}
            train_set.construct()
            get_manager().phase = "first_call"
            self._train_set = train_set
            objective = create_objective(cfg)
            metrics = [m for m in (create_metric(nm, cfg) for nm in cfg.metric)
                       if m is not None]
            self._gbdt = create_boosting(cfg.boosting)
            self._gbdt.init(cfg, train_set._handle, objective, metrics)
            self.config = cfg
        elif model_file is not None:
            with open(model_file) as fh:
                model_str = fh.read()
            self._init_from_string(model_str)
        elif model_str is not None:
            self._init_from_string(model_str)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create booster instance")

    def _init_from_string(self, model_str: str) -> None:
        from .boosting.gbdt import GBDT
        self._gbdt = GBDT()
        self._gbdt.load_model_from_string(model_str)
        self.config = Config.from_params(self.params) if self.params else Config()

    # -- pickling (reference basic.py Booster.__getstate__: the model
    # string IS the state; the device engine is rebuilt on load) -------
    def __getstate__(self):
        return {
            "model_str": self.model_to_string(num_iteration=-1),
            "params": self.params,
            "best_iteration": self.best_iteration,
            "best_score": self.best_score,
        }

    def __setstate__(self, state):
        self.params = state.get("params", {})
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self._train_set = None
        # validation DATA does not survive pickling; an empty name list
        # makes eval(..., name) raise the clear "No validation set"
        # error instead of silently returning no metrics
        self.name_valid_sets = []
        self._network_initialized = False
        self._init_from_string(state["model_str"])

    # ------------------------------------------------------------------
    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120, num_machines: int = 1) -> "Booster":
        """Multi-host wiring (reference basic.py:2093 set_network ->
        LGBM_NetworkInit, c_api.cpp:2262). On TPU the collective STACK
        is XLA's (psum/all_gather over ICI/DCN); what this call does is
        the process wiring: `jax.distributed.initialize` with the rank
        discovered from the machine list, fusing every host's chips
        into the one global device set (lightgbm_tpu.network; launch
        recipe in docs/MULTIHOST.md)."""
        from .network import ensure_distributed
        if isinstance(machines, (list, set)):
            machines = ",".join(str(m) for m in machines)
        ensure_distributed(machines, num_machines,
                           time_out=listen_time_out)
        self._network_initialized = True
        return self

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if not isinstance(data, Dataset):
            raise TypeError(f"Validation data should be Dataset instance, "
                            f"met {type(data).__name__}")
        data.construct()
        from .metric.metrics import create_metric
        metrics = [m for m in (create_metric(nm, self.config)
                               for nm in self.config.metric) if m is not None]
        self._gbdt.add_valid_data(data._handle, metrics)
        self.name_valid_sets.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True if stopped
        (reference basic.py:2315)."""
        if train_set is not None and train_set is not self._train_set:
            raise LightGBMError("Replacing train_set is not supported yet")
        with obs.span("update"):
            if fobj is None:
                stopped = self._gbdt.train_one_iter()
            else:
                grad, hess = fobj(self._curr_pred_for_fobj(),
                                  self._train_set)
                stopped = self.__boost(grad, hess)
        get_manager().mark_update()
        if not self._setup_reported:
            # the first iteration paid for the state and the compile
            self._setup_reported = True
            log.info("%s", obs.setup_line())
        return stopped

    def _curr_pred_for_fobj(self):
        """Raw training scores handed to a custom fobj: [N] for
        single-class, [N, K] otherwise (reference passes the flat score
        array through LGBM_BoosterGetPredict)."""
        score = np.asarray(self._gbdt.get_training_score(), dtype=np.float64)
        k = self._gbdt.num_tree_per_iteration
        return score[0] if k == 1 else score.T

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, dtype=np.float32)
        hess = np.asarray(hess, dtype=np.float32)
        k = self._gbdt.num_tree_per_iteration
        n = self._gbdt.num_data
        if grad.ndim == 2:  # [N, K] sklearn layout -> [K, N]
            grad, hess = grad.T, hess.T
        if grad.size != n * k:
            raise ValueError(
                f"Length of gradient ({grad.size}) doesn't match "
                f"num_data*num_class ({n * k})")
        return self._gbdt.train_one_iter(grad.reshape(k, n), hess.reshape(k, n))

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self):
        return self._gbdt.current_iteration

    def num_trees(self) -> int:
        return len(self._gbdt.models)

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    def num_feature(self) -> int:
        return self._gbdt.max_feature_idx + 1

    def feature_name(self) -> List[str]:
        return list(self._gbdt.feature_names_)

    # ------------------------------------------------------------------
    def eval(self, data: Dataset, name: str, feval=None):
        if data is self._train_set:
            return self.eval_train(feval)
        try:
            idx = self.name_valid_sets.index(name)
        except ValueError:
            raise LightGBMError(f"No validation set named {name}")
        return self._eval_set(f"valid_{idx}", name, feval)

    def eval_train(self, feval=None, res=None):
        return self._eval_set("training", "training", feval, res=res)

    def eval_valid(self, feval=None, res=None):
        out = []
        for i, name in enumerate(self.name_valid_sets):
            out += self._eval_set(f"valid_{i}", name, feval, res=res)
        return out

    def _eval_set(self, key: str, display_name: str, feval=None, res=None):
        # `res` lets the pipelined engine loop resolve ONE
        # begin_eval_at_iter handle and fan its rows out to every
        # dataset filter, instead of re-evaluating per call
        if res is None:
            res = self._gbdt.eval_at_iter()
        out = [(display_name, mname, val, bib)
               for ds, mname, val, bib in res if ds == key]
        if feval is not None:
            fevals = feval if isinstance(feval, list) else [feval]
            for f in fevals:
                if key == "training":
                    pred = self._inner_predict_train()
                    dset = self._train_set
                else:
                    idx = int(key.split("_")[1])
                    pred = self._inner_predict_valid(idx)
                    dset = None
                ret = f(pred, dset)
                rets = [ret] if not isinstance(ret, list) else ret
                for nm, val, bib in rets:
                    out.append((display_name, nm, val, bib))
        return out

    def _inner_predict_train(self):
        score = np.asarray(self._gbdt.get_training_score(), dtype=np.float64)
        return self._conv_eval_scores(score)

    def _inner_predict_valid(self, idx):
        score = np.asarray(self._gbdt.valid_score[idx].score, dtype=np.float64)
        return self._conv_eval_scores(score)

    def _conv_eval_scores(self, score):
        k = self._gbdt.num_tree_per_iteration
        if self._gbdt.objective is not None:
            import jax.numpy as jnp
            # tpulint: sync-ok(eval-path output conversion, once per eval call)
            conv = np.asarray(self._gbdt.objective.convert_output(
                jnp.asarray(score[0] if k == 1 else score.T)))
            return conv
        return score[0] if k == 1 else score.T

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, data_has_header: bool = False,
                is_reshape: bool = True, **kwargs) -> np.ndarray:
        mat = _to_2d_numpy(data)
        if num_iteration is None:
            num_iteration = -1
        if _is_sparse(mat):
            # inference traverses raw feature values; densify sparse
            # inputs in bounded row chunks (reference predicts CSR rows
            # one at a time through the same raw-value decision path)
            csr = mat.tocsr()
            n = csr.shape[0]
            chunk = max(1024, min(max(n, 1), 1 << 16))
            parts = [self._predict_dense(
                np.asarray(csr[i:i + chunk].todense(), dtype=np.float64),
                start_iteration, num_iteration, raw_score, pred_leaf,
                pred_contrib) for i in range(0, n, chunk)]
            if not parts:
                return self._predict_dense(
                    np.zeros((0, csr.shape[1])), start_iteration,
                    num_iteration, raw_score, pred_leaf, pred_contrib)
            return np.concatenate(parts, axis=0)
        return self._predict_dense(mat, start_iteration, num_iteration,
                                   raw_score, pred_leaf, pred_contrib)

    def _predict_dense(self, mat, start_iteration, num_iteration,
                       raw_score, pred_leaf, pred_contrib) -> np.ndarray:
        if pred_leaf:
            return self._gbdt.predict_leaf_index(mat, start_iteration, num_iteration)
        if pred_contrib:
            return self._gbdt.predict_contrib(mat, start_iteration, num_iteration)
        if raw_score:
            return self._gbdt.predict_raw(mat, start_iteration, num_iteration)
        return self._gbdt.predict(mat, start_iteration, num_iteration)

    def refit(self, data, label, decay_rate: float = 0.9, **kwargs) -> "Booster":
        """reference basic.py:2873 Booster.refit."""
        mat = _to_2d_numpy(data)
        self._gbdt._materialize_models()
        leaf = self.predict(data, pred_leaf=True)
        new_params = dict(self.params)
        new_params["refit_decay_rate"] = decay_rate
        train = Dataset(mat, label=label, params=new_params,
                        free_raw_data=False)
        nb = Booster(new_params, train)
        nb._gbdt.models = [copy_tree(t) for t in self._gbdt.models]
        nb._gbdt.refit_tree(leaf)
        return nb

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        it = self.best_iteration if num_iteration is None else num_iteration
        self._gbdt.save_model_to_file(
            filename, start_iteration, it if it and it > 0 else -1,
            0 if importance_type == "split" else 1)
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        it = self.best_iteration if num_iteration is None else num_iteration
        return self._gbdt.save_model_to_string(
            start_iteration, it if it and it > 0 else -1,
            0 if importance_type == "split" else 1)

    @classmethod
    def model_from_string(cls, model_str: str, verbose: bool = True) -> "Booster":
        return cls(model_str=model_str)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> dict:
        g = self._gbdt
        it = self.best_iteration if num_iteration is None else num_iteration
        models = g._used_models(start_iteration, it if it and it > 0 else -1)
        return {
            "name": "tree",
            "version": "v3",
            "num_class": getattr(g, "_loaded_num_class",
                                 g.config.num_class if g.config else 1),
            "num_tree_per_iteration": g.num_tree_per_iteration,
            "label_index": g.label_idx,
            "max_feature_idx": g.max_feature_idx,
            "objective": g.objective.to_string() if g.objective else "",
            "average_output": g.average_output,
            "feature_names": list(g.feature_names_),
            "feature_infos": g._feature_infos(),
            "tree_info": [dict(tree_index=i, **t.to_json())
                          for i, t in enumerate(models)],
        }

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        imp = self._gbdt.feature_importance(
            0 if importance_type == "split" else 1,
            iteration if iteration else -1)
        if importance_type == "split":
            return imp.astype(np.int32)
        return imp

    def get_split_value_histogram(self, feature, bins=None, xgboost_style=False):
        """reference basic.py:2944."""
        if isinstance(feature, str):
            fidx = self.feature_name().index(feature)
        else:
            fidx = int(feature)
        self._gbdt._materialize_models()
        values = []
        for t in self._gbdt.models:
            ni = t.num_leaves - 1
            for i in range(ni):
                if int(t.split_feature[i]) == fidx and not t.is_categorical_node(i):
                    values.append(float(t.threshold[i]))
        values = np.asarray(values)
        if bins is None:
            bins = max(min(len(values), 32), 1)
        hist, edges = np.histogram(values, bins=bins)
        if xgboost_style:
            import pandas as pd
            return pd.DataFrame({"SplitValue": edges[1:], "Count": hist})
        return hist, edges

    def trees_to_dataframe(self):
        """reference basic.py:2132."""
        import pandas as pd
        self._gbdt._materialize_models()
        rows = []
        fn = self.feature_name()
        for ti, t in enumerate(self._gbdt.models):
            ni = t.num_leaves - 1
            for i in range(ni):
                rows.append({
                    "tree_index": ti, "node_depth": None,
                    "node_index": f"{ti}-S{i}",
                    "left_child": f"{ti}-S{t.left_child[i]}" if t.left_child[i] >= 0
                    else f"{ti}-L{~t.left_child[i]}",
                    "right_child": f"{ti}-S{t.right_child[i]}" if t.right_child[i] >= 0
                    else f"{ti}-L{~t.right_child[i]}",
                    "parent_index": None,
                    "split_feature": fn[int(t.split_feature[i])],
                    "split_gain": float(t.split_gain[i]),
                    "threshold": float(t.threshold[i]),
                    "decision_type": "==" if t.is_categorical_node(i) else "<=",
                    "missing_direction": "left" if t.default_left(i) else "right",
                    "missing_type": ["None", "Zero", "NaN"][t.missing_type(i)],
                    "value": float(t.internal_value[i]),
                    "weight": float(t.internal_weight[i]),
                    "count": int(t.internal_count[i]),
                })
            for leaf in range(t.num_leaves):
                rows.append({
                    "tree_index": ti, "node_depth": None,
                    "node_index": f"{ti}-L{leaf}",
                    "left_child": None, "right_child": None,
                    "parent_index": None, "split_feature": None,
                    "split_gain": None, "threshold": None,
                    "decision_type": None, "missing_direction": None,
                    "missing_type": None,
                    "value": float(t.leaf_value[leaf]),
                    "weight": float(t.leaf_weight[leaf]),
                    "count": int(t.leaf_count[leaf]),
                })
        return pd.DataFrame(rows)

    def free_dataset(self) -> "Booster":
        self._train_set = None
        return self

    def free_network(self) -> "Booster":
        self._network_initialized = False
        return self


def copy_tree(tree):
    import copy as _copy
    t = _copy.copy(tree)
    t.leaf_value = tree.leaf_value.copy()
    t.internal_value = tree.internal_value.copy()
    t._device = None
    return t
