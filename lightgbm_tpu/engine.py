"""Training entry points: train() and cv().

API-compatible re-implementation of the reference engine
(reference: python-package/lightgbm/engine.py — train() at :18 with the
callback/early-stopping protocol, cv() at :394 with stratified folds and
CVBooster at :280).
"""
from __future__ import annotations

import collections
import copy
import os
from typing import Any, Dict, List, Optional

import numpy as np

from . import callback as callback_mod
from . import obs
from .basic import Booster, Dataset, LightGBMError
from .config import Config, _ALIASES
from .utils import log


def _resolve_num_boost_round(params: Dict[str, Any], default: int) -> int:
    for alias in ("num_iterations", "num_iteration", "n_iter", "num_tree",
                  "num_trees", "num_round", "num_rounds", "num_boost_round",
                  "n_estimators"):
        if alias in params:
            return int(params.pop(alias))
    return default


def _resolve_early_stopping(params: Dict[str, Any],
                            explicit: Optional[int]) -> Optional[int]:
    for alias in ("early_stopping_round", "early_stopping_rounds",
                  "early_stopping", "n_iter_no_change"):
        if alias in params:
            return int(params.pop(alias))
    return explicit


def _telemetry_end_iteration(telemetry, booster, iteration: int,
                             evals) -> None:
    """Snapshot one iteration into the telemetry session: sync the
    device stream first (metrics mode only — the disabled path never
    pays this) so the wall time is honest, then attach model stats and
    eval metrics."""
    import jax
    gbdt = booster._gbdt
    extra: Dict[str, Any] = {}
    if not telemetry.record_consumers_active():
        # every record consumer is gone (the sink died on an I/O error,
        # nothing else is on): don't pay the stream sync + device stat
        # fetches just to format a payload that gets dropped — the
        # registry still keeps its lifecycle and counts the drop
        telemetry.end_iteration(iteration)
        return
    try:
        with obs.span("telemetry stream sync", phase="sync"):
            # tpulint: sync-ok(telemetry-only stream sync for honest wall time)
            jax.block_until_ready(gbdt.device_score_state())
    except Exception:
        pass
    try:
        with obs.span("telemetry stats", phase="telemetry"):
            extra.update(gbdt.telemetry_stats())
    except Exception as exc:
        log.debug("telemetry_stats failed: %s", exc)
    if evals:
        extra["metrics"] = {f"{ds}/{m}": float(v)
                            for ds, m, v, _ in evals}
    telemetry.end_iteration(iteration, extra=extra)


def _checkpoint_capture(booster: Booster, cbs) -> tuple:
    """(state, model_text) snapshot of everything resume needs: the
    boosting loop state (gbdt.checkpoint_state), each checkpoint-aware
    callback's state (keyed by its checkpoint_key), and the running
    best_iteration. The model itself travels as the reference text
    format, so a checkpoint is also a valid saved model."""
    gbdt = booster._gbdt
    state: Dict[str, Any] = {
        "gbdt": gbdt.checkpoint_state(),
        "best_iteration": int(booster.best_iteration),
        "callbacks": {},
    }
    for cb in cbs:
        key = getattr(cb, "checkpoint_key", None)
        if key and hasattr(cb, "checkpoint_state"):
            state["callbacks"][key] = cb.checkpoint_state()
    return state, gbdt.save_model_to_string()


def _checkpoint_restore(booster: Booster, cbs, state: Dict[str, Any],
                        model_text: str) -> None:
    booster._gbdt.restore_checkpoint_state(state["gbdt"], model_text)
    booster.best_iteration = int(state.get("best_iteration", -1))
    cb_states = state.get("callbacks", {})
    for cb in cbs:
        key = getattr(cb, "checkpoint_key", None)
        if key and key in cb_states \
                and hasattr(cb, "restore_checkpoint_state"):
            cb.restore_checkpoint_state(cb_states[key])


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100, valid_sets=None, valid_names=None,
          fobj=None, feval=None, init_model=None, feature_name: str = "auto",
          categorical_feature: str = "auto",
          early_stopping_rounds: Optional[int] = None, evals_result=None,
          verbose_eval=True, learning_rates=None,
          keep_training_booster: bool = False, callbacks=None,
          checkpoint_dir: Optional[str] = None) -> Booster:
    """reference engine.py:18.

    `checkpoint_dir` (also settable as the `checkpoint_dir` param)
    enables preemption-safe training: atomic checkpoints every
    `checkpoint_interval` iterations, and auto-resume from the latest
    valid checkpoint when one exists (docs/ROBUSTNESS.md)."""
    params = copy.deepcopy(params) if params else {}
    from .compile import ensure_compile_cache, preload_store_async
    ensure_compile_cache()
    preload_store_async()
    # multi-host process wiring BEFORE any dataset construction, so the
    # distributed bin-mapper allgather and the training mesh see the
    # global device set (reference Application::InitTrain calls
    # Network::Init first, application.cpp:164-175). Alias resolution
    # goes through Config so "workers"/"nodes"/"num_machine" work here
    # exactly as everywhere else.
    net_cfg = Config.from_params({
        k: v for k, v in params.items()
        if Config.resolve_alias(k) in ("num_machines", "machines",
                                       "time_out")})
    if net_cfg.num_machines > 1:
        # with an empty machine list this is env-driven
        # (JAX_COORDINATOR_ADDRESS) or a single-controller no-op —
        # ensure_distributed sorts the cases out
        from .network import ensure_distributed
        ensure_distributed(net_cfg.machines, net_cfg.num_machines,
                           time_out=net_cfg.time_out)
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    if num_boost_round <= 0:
        raise ValueError("num_boost_round should be greater than zero.")
    from .utils.timer import global_timer
    _timetag = [v for k, v in params.items()
                if Config.resolve_alias(k) == "timetag"]
    if _timetag:
        # explicit per-train toggle wins over env/verbosity
        from .config import _parse_bool
        global_timer.set_enabled(_parse_bool(_timetag[0]))
    elif not os.environ.get("LGBM_TPU_TIMETAG"):
        # reference -DUSE_TIMETAG phase table (common.h:1054): opt-in
        # via the env knob or verbose>=2 (assign BOTH ways so a quiet
        # train after a verbose one stops paying the annotations)
        global_timer.enabled =             int(params.get("verbose", params.get("verbosity", 1)) or 0) >= 2

    early_stopping_rounds = _resolve_early_stopping(params, early_stopping_rounds)
    first_metric_only = params.get("first_metric_only", False)

    if fobj is not None:
        params["objective"] = "none"
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    predictor_model = None
    if init_model is not None:
        if isinstance(init_model, str):
            predictor_model = Booster(model_file=init_model)
        elif isinstance(init_model, Booster):
            predictor_model = init_model

    # continued training: initialize train/valid scores by predicting the
    # old model over the raw data (reference basic.py
    # _set_init_score_by_predictor:1019)
    if predictor_model is not None and train_set.init_score is None:
        raw = train_set.data
        if raw is None:
            raise LightGBMError("Cannot continue training when the raw data "
                                "was freed; pass free_raw_data=False")
        init_score = predictor_model.predict(raw, raw_score=True)
        train_set.init_score = init_score.T.reshape(-1) if init_score.ndim == 2 \
            else init_score

    with obs.span("dataset construction + learner build"):
        booster = Booster(params=params, train_set=train_set)
    plan = booster._gbdt.execution_plan()
    log.info("Training on backend=%s (%s x%d): tier=%s learner=%s "
             "hist=%s partition=%s", plan["backend"], plan["device_kind"],
             plan["device_count"], plan["tier"], plan["learner"],
             plan["hist"], plan["partition"])
    if booster._gbdt.config.device_type == "tpu" \
            and plan["backend"] != "tpu":
        log.warning("device_type=tpu but JAX initialised the %s backend, "
                    "where the TPU kernels cannot be compiled: training "
                    "runs hist=%s partition=%s there", plan["backend"],
                    plan["hist"], plan["partition"])
    from .compile import background_warmup, warmup_wanted
    if warmup_wanted(booster._gbdt.config, train_set.num_data()):
        # compile the registered entry specs on a thread pool while the
        # caller is still wiring callbacks/valid sets; the first training
        # iteration then dispatches straight into warm executables
        background_warmup()
    if predictor_model is not None:
        k = predictor_model._gbdt.num_tree_per_iteration
        from .basic import copy_tree
        predictor_model._gbdt._materialize_models()
        booster._gbdt.models = [copy_tree(t) for t in predictor_model._gbdt.models] \
            + booster._gbdt.models
        booster._gbdt.num_init_iteration = len(predictor_model._gbdt.models) // k
        booster._gbdt.iter = 0

    valid_contain_train = False
    train_data_name = "training"
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if valid_names is not None and isinstance(valid_names, str):
            valid_names = [valid_names]
        for i, vs in enumerate(valid_sets):
            if vs is train_set:
                valid_contain_train = True
                if valid_names is not None:
                    train_data_name = valid_names[i]
                continue
            if predictor_model is not None and vs.init_score is None \
                    and vs.data is not None:
                isc = predictor_model.predict(vs.data, raw_score=True)
                vs.init_score = isc.T.reshape(-1) if isc.ndim == 2 else isc
            name = valid_names[i] if valid_names is not None else f"valid_{i}"
            booster.add_valid(vs, name)

    cbs = set(callbacks) if callbacks else set()
    if verbose_eval is True:
        cbs.add(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval is not False:
        cbs.add(callback_mod.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback_mod.early_stopping(early_stopping_rounds,
                                            first_metric_only,
                                            verbose=bool(verbose_eval)))
    if learning_rates is not None:
        cbs.add(callback_mod.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        cbs.add(callback_mod.record_evaluation(evals_result))

    callbacks_before = {cb for cb in cbs if getattr(cb, "before_iteration", False)}
    callbacks_after = cbs - callbacks_before
    callbacks_before = sorted(callbacks_before, key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted(callbacks_after, key=lambda cb: getattr(cb, "order", 0))

    # preemption safety (docs/ROBUSTNESS.md): periodic atomic
    # checkpoints + auto-resume. Wired AFTER callback assembly so
    # checkpoint-aware callbacks (early stopping, record_evaluation)
    # can hand their state back on resume.
    from .robust.checkpoint import CheckpointManager
    from .robust.faultinject import check_fault
    cfg = booster._gbdt.config
    ckpt_dir = checkpoint_dir if checkpoint_dir else cfg.checkpoint_dir
    ckpt_mgr = None
    start_iteration = 0
    if ckpt_dir:
        from .compile import signature as S
        digest = S._digest(S.config_signature(cfg))
        ckpt_mgr = CheckpointManager(
            ckpt_dir, interval=cfg.checkpoint_interval,
            keep=cfg.checkpoint_keep, params_digest=digest)
        if init_model is None:
            resumed = ckpt_mgr.load_latest()
            if resumed is not None:
                it, ck_state, ck_model = resumed
                _checkpoint_restore(booster, cbs, ck_state, ck_model)
                start_iteration = it + 1
                log.info("Resuming from checkpoint %s: %d iterations "
                         "already trained", ckpt_mgr.path_for(it),
                         start_iteration)
        else:
            # reference init_model semantics win: an explicit warm
            # start means the caller is managing continuation itself
            log.warning("checkpoint_dir=%s ignored for resume because "
                        "init_model was given (checkpoints will still "
                        "be written)", ckpt_dir)

    telemetry = obs.TelemetrySession.from_config(booster._gbdt.config)
    if telemetry is not None:
        telemetry.start()
        telemetry.registry.set_gauge("train.total_iterations",
                                     float(num_boost_round))
    # dispatch-ahead pipelining (default; LGBM_TPU_PIPELINE=0 restores
    # the fully synchronous loop): iteration t's eval-scalar readback
    # and after-iteration callbacks run only after iteration t+1's
    # device work has been dispatched, so the host never idles waiting
    # for metrics. Early stopping therefore observes iteration t one
    # step late — it can never stop EARLIER than the synchronous loop,
    # trains at most one extra tree, and records the same
    # best_iteration (which the saved model is truncated to, so saved
    # output is identical). Full telemetry mode stays synchronous: its
    # per-iteration stream sync serializes the loop anyway, and every
    # JSONL record must carry its own iteration's metrics. LIGHTWEIGHT
    # sessions (obs_port / flight_dir only, no metrics_file) ride the
    # pipelined loop: their per-iteration bookkeeping is host-side
    # registry arithmetic plus at most the one fleet allgather, never a
    # stream sync or a device stat fetch.
    # feval also forces the synchronous loop: a custom eval reads the
    # LIVE score arrays at call time, so a deferred call would see the
    # next iteration's scores
    full_telemetry = telemetry is not None and not telemetry.lightweight
    pipeline = (not full_telemetry and feval is None
                and os.environ.get("LGBM_TPU_PIPELINE", "1") != "0")
    evaluation_result_list: Optional[list] = None
    pending = None    # (iteration, unresolved eval handle)

    def _resolve_evals(handle) -> list:
        evals: list = []
        with obs.span("metric evaluation (resolve)", phase="eval"):
            res = booster._gbdt.finish_eval_at_iter(handle) \
                if handle is not None else None
            if valid_contain_train:
                evals.extend((train_data_name, m, v, b)
                             for _, m, v, b
                             in booster.eval_train(feval, res=res))
            if booster.name_valid_sets:
                evals.extend(booster.eval_valid(feval, res=res))
        return evals

    def _after_callbacks(it: int, evals) -> None:
        with watch_phase("host-callback:after"):
            for cb in callbacks_after:
                cb(callback_mod.CallbackEnv(model=booster, params=params,
                                            iteration=it, begin_iteration=0,
                                            end_iteration=num_boost_round,
                                            evaluation_result_list=evals))

    # self-healing (docs/ROBUSTNESS.md): a hang watchdog arms a deadman
    # timer over the loop; numeric-sentinel verdicts ride the trailing
    # fetches; the recovery policy below quarantines bad trees, rolls
    # back to the last checkpoint, and steps down the degraded-mode
    # ladder instead of hanging forever or training garbage
    from .robust.sentinel import apply_degraded_rung
    from .robust.watchdog import (HangTimeout, Watchdog, activate_watchdog,
                                  deactivate_watchdog, watch_phase)
    wd = None
    if cfg.hang_timeout > 0:
        wd = Watchdog(cfg.hang_timeout,
                      trace_path=(cfg.trace_file + ".watchdog.json"
                                  if cfg.trace_file
                                  else "watchdog_trace.json"),
                      # the first iterations block on whole-program
                      # compiles; a short timeout must not call that a
                      # hang (and there is no checkpoint to resume from
                      # yet)
                      warmup_grace_s=max(60.0, 4 * cfg.hang_timeout))
        activate_watchdog(wd)
        wd.start()
    resume_attempts = 0
    degraded_rung = 0

    def _restore_latest() -> bool:
        """Roll the LIVE booster back to the newest checkpoint; updates
        start_iteration for loop re-entry. In-flight eval handles are
        dropped — they belong to the abandoned timeline."""
        nonlocal start_iteration, pending
        if ckpt_mgr is None:
            return False
        resumed = ckpt_mgr.load_latest()
        if resumed is None:
            return False
        pending = None
        it, ck_state, ck_model = resumed
        _checkpoint_restore(booster, cbs, ck_state, ck_model)
        start_iteration = it + 1
        return True
    try:
      while True:
        restart = False
        try:
            for i in range(start_iteration, num_boost_round):
                if wd is not None:
                    wd.beat(i)
                    wd.check()
                spec = check_fault("train.iteration", index=i)
                if spec is not None and spec.mode in ("nan", "overflow"):
                    # drill: the next gradient plane is poisoned; the
                    # numeric sentinels must catch the divergence
                    booster._gbdt._poison_next = spec.mode
                if telemetry is not None:
                    telemetry.begin_iteration(i)
                with obs.span("before-iteration callbacks",
                              phase="callbacks"), \
                        watch_phase("host-callback:before"):
                    for cb in callbacks_before:
                        cb(callback_mod.CallbackEnv(
                            model=booster, params=params, iteration=i,
                            begin_iteration=0,
                            end_iteration=num_boost_round,
                            evaluation_result_list=None))
                with obs.span("boosting iteration (device dispatch)",
                              phase="update"), \
                        watch_phase("dispatch:update"):
                    finished = booster.update(fobj=fobj)

                with obs.span("metric evaluation", phase="eval"):
                    eval_handle = (
                        booster._gbdt.begin_eval_at_iter()
                        if valid_contain_train or booster.name_valid_sets
                        else None)
                if full_telemetry:
                    evaluation_result_list = _resolve_evals(eval_handle)
                    eval_handle = None
                    _telemetry_end_iteration(telemetry, booster, i,
                                             evaluation_result_list)
                elif telemetry is not None:
                    # lightweight: registry wall-clock + fleet merge +
                    # SLO check only — no stream sync, no device fetch;
                    # the window ends at dispatch, trailing resolve time
                    # is attributed to the next iteration
                    telemetry.end_iteration(i)
                drained_it = i
                try:
                    if full_telemetry:
                        _after_callbacks(i, evaluation_result_list)
                    else:
                        # trailing resolve: the PREVIOUS iteration's eval
                        # readback and callbacks run while this iteration's
                        # device work is already in flight
                        if pending is not None:
                            pit, ph = pending
                            pending = None
                            drained_it = pit
                            evaluation_result_list = _resolve_evals(ph)
                            _after_callbacks(pit, evaluation_result_list)
                        pending = (i, eval_handle)
                        if not pipeline or finished:
                            pit, ph = pending
                            pending = None
                            drained_it = pit
                            evaluation_result_list = _resolve_evals(ph)
                            _after_callbacks(pit, evaluation_result_list)
                except callback_mod.EarlyStopException as e:
                    booster.best_iteration = e.best_iteration + 1
                    evaluation_result_list = e.best_score
                    if drained_it < i:
                        reg = obs.active()
                        if reg is not None:
                            # the stop decision arrived one dispatch late:
                            # iteration i was already trained (and is
                            # truncated away through best_iteration)
                            reg.inc("pipeline.delayed_stop_iters")
                    break
                sent = booster._gbdt._sentinel
                if sent is not None \
                        and booster._gbdt.process_sentinel_trips():
                    # repeated numeric trips: quarantine was not enough,
                    # so roll back to the last checkpoint and give up
                    # one optimization rung per recovery epoch
                    rung = apply_degraded_rung(booster._gbdt,
                                               degraded_rung)
                    if rung is not None:
                        degraded_rung += 1
                    if _restore_latest():
                        reg = obs.active()
                        if reg is not None:
                            reg.inc("health.rollbacks")
                        sent.drop_pending()
                        sent.reset_trips()
                        log.warning(
                            "sentinel: rolled back to iteration %d after "
                            "%d numeric-health trips", start_iteration,
                            sent.total_trips)
                        restart = True
                        break
                    # no checkpoint to return to: the offending trees
                    # are already quarantined, keep training degraded
                    sent.reset_trips()
                if finished:
                    break
                if ckpt_mgr is not None and ckpt_mgr.due(i):
                    # the pipelined loop drains first: callback state and
                    # eval records must cover iteration i before capture,
                    # exactly as the synchronous order would have them
                    if pending is not None:
                        try:
                            pit, ph = pending
                            pending = None
                            evaluation_result_list = _resolve_evals(ph)
                            _after_callbacks(pit, evaluation_result_list)
                        except callback_mod.EarlyStopException as e:
                            booster.best_iteration = e.best_iteration + 1
                            evaluation_result_list = e.best_score
                            break
                    with obs.span("checkpoint save", phase="checkpoint"):
                        ck_state, ck_model = _checkpoint_capture(booster, cbs)
                        ckpt_mgr.save(i, ck_state, ck_model)
            if restart:
                continue
            # post-loop drain: the final iteration's callbacks (including
            # the early-stopper's is-last announcement) when the loop ran
            # to its end with an iteration still in flight
            if pending is not None:
                try:
                    pit, ph = pending
                    pending = None
                    evaluation_result_list = _resolve_evals(ph)
                    _after_callbacks(pit, evaluation_result_list)
                except callback_mod.EarlyStopException as e:
                    booster.best_iteration = e.best_iteration + 1
                    evaluation_result_list = e.best_score
            break
        except HangTimeout:
            resume_attempts += 1
            if not cfg.auto_resume \
                    or resume_attempts > cfg.auto_resume_attempts \
                    or not _restore_latest():
                # no checkpoint (or attempts exhausted): surface the
                # watchdog's classified, actionable diagnosis
                raise
            if booster._gbdt._sentinel is not None:
                booster._gbdt._sentinel.drop_pending()
            if wd is not None:
                wd.clear()
            reg = obs.active()
            if reg is not None:
                reg.inc("watchdog.auto_resume")
            log.warning(
                "watchdog: auto-resuming from iteration %d after a "
                "detected hang (attempt %d/%d)", start_iteration,
                resume_attempts, cfg.auto_resume_attempts)
      # resolve any sentinel verdicts still in flight so a trip on the
      # final trees still quarantines them before the model is
      # finalized — before the finally below deactivates the flight
      # recorder, so a tail-end trip still dumps its evidence bundle
      if getattr(booster._gbdt, "_sentinel", None) is not None:
          booster._gbdt.sentinel_drain()
          booster._gbdt.process_sentinel_trips()
    finally:
        if wd is not None:
            deactivate_watchdog(wd)
            wd.stop()
        if telemetry is not None:
            telemetry.close()

    # fused path trains blind between periodic stop checks; drop any
    # trailing all-degenerate iterations it may have accumulated
    if getattr(booster._gbdt, "_fused", None) is not None:
        with obs.span("degenerate-tail check (device sync)"):
            booster._gbdt._trim_degenerate_tail()
    if global_timer.enabled and global_timer.acc:
        from .utils import log as _log
        _log.info("%s", global_timer.report())
        global_timer.reset()   # per-train tables; also avoids the
        # atexit re-print of already-reported scopes

    for ds_name, m_name, val, _ in (evaluation_result_list or []):
        booster.best_score.setdefault(ds_name, collections.OrderedDict())
        booster.best_score[ds_name][m_name] = val
    if not keep_training_booster:
        booster.free_dataset()
    return booster


class CVBooster:
    """Ensemble of per-fold boosters (reference engine.py:280)."""

    def __init__(self) -> None:
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name: str):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict,
                  seed: int, fpreproc=None, stratified: bool = True,
                  shuffle: bool = True, eval_train_metric: bool = False):
    full_data = full_data.construct()
    num_data = full_data.num_data()
    group = full_data.get_group()
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError("folds should be a generator or iterator of "
                                 "(train_idx, test_idx) tuples or scikit-learn splitter")
        if hasattr(folds, "split"):
            folds = folds.split(X=np.empty(num_data), y=full_data.get_label(),
                                groups=None)
    else:
        if group is not None:
            # group-aware folds: whole queries assigned to folds
            ng = len(group)
            rng = np.random.RandomState(seed)
            gidx = rng.permutation(ng) if shuffle else np.arange(ng)
            bounds = np.concatenate([[0], np.cumsum(group)]).astype(np.int64)
            fold_groups = np.array_split(gidx, nfold)
            folds = []
            for k in range(nfold):
                test_g = set(fold_groups[k].tolist())
                test_idx = np.concatenate(
                    [np.arange(bounds[g], bounds[g + 1]) for g in sorted(test_g)]) \
                    if test_g else np.empty(0, np.int64)
                train_idx = np.setdiff1d(np.arange(num_data), test_idx)
                folds.append((train_idx, test_idx))
        elif stratified:
            label = full_data.get_label()
            rng = np.random.RandomState(seed)
            folds = []
            classes = np.unique(label)
            assign = np.empty(num_data, dtype=np.int64)
            for c in classes:
                rows = np.flatnonzero(label == c)
                if shuffle:
                    rng.shuffle(rows)
                assign[rows] = np.arange(len(rows)) % nfold
            for k in range(nfold):
                test_idx = np.flatnonzero(assign == k)
                train_idx = np.flatnonzero(assign != k)
                folds.append((train_idx, test_idx))
        else:
            rng = np.random.RandomState(seed)
            idx = rng.permutation(num_data) if shuffle else np.arange(num_data)
            parts = np.array_split(idx, nfold)
            folds = [(np.setdiff1d(np.arange(num_data), p), np.sort(p))
                     for p in parts]

    ret = CVBooster()
    for train_idx, test_idx in folds:
        train_sub = full_data.subset(np.sort(train_idx))
        valid_sub = full_data.subset(np.sort(test_idx))
        if group is not None:
            bounds = np.concatenate([[0], np.cumsum(group)]).astype(np.int64)
            qid_of_row = np.searchsorted(bounds, np.arange(num_data), side="right") - 1
            tq = qid_of_row[np.sort(train_idx)]
            vq = qid_of_row[np.sort(test_idx)]
            train_sub.group = np.bincount(tq)[np.unique(tq)]
            valid_sub.group = np.bincount(vq)[np.unique(vq)]
        tparams = params
        if fpreproc is not None:
            train_sub, valid_sub, tparams = fpreproc(train_sub, valid_sub,
                                                     copy.deepcopy(params))
        booster = Booster(tparams, train_sub)
        if eval_train_metric:
            booster.add_valid(train_sub, "train")
        booster.add_valid(valid_sub, "valid")
        ret._append(booster)
    return ret


def _agg_cv_result(raw_results, eval_train_metric: bool = False):
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            if eval_train_metric:
                key = f"{one_line[0]} {one_line[1]}"
            else:
                key = one_line[1]
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k], float(np.std(v)))
            for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name: str = "auto", categorical_feature: str = "auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False):
    """reference engine.py:394."""
    from .compile import ensure_compile_cache, preload_store_async
    ensure_compile_cache()
    preload_store_async()
    params = copy.deepcopy(params) if params else {}
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    early_stopping_rounds = _resolve_early_stopping(params, early_stopping_rounds)
    first_metric_only = params.get("first_metric_only", False)
    if fobj is not None:
        params["objective"] = "none"
    if metrics is not None:
        params["metric"] = metrics
    if isinstance(params.get("objective"), str) and \
            params["objective"] in ("lambdarank", "rank_xendcg"):
        stratified = False

    results = collections.defaultdict(list)
    cvfolds = _make_n_folds(train_set, folds, nfold, params, seed, fpreproc,
                            stratified, shuffle, eval_train_metric)

    cbs = set(callbacks) if callbacks else set()
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback_mod.early_stopping(early_stopping_rounds,
                                            first_metric_only, verbose=False))
    if verbose_eval is True:
        cbs.add(callback_mod.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and verbose_eval is not False:
        cbs.add(callback_mod.print_evaluation(verbose_eval, show_stdv))
    callbacks_before = {cb for cb in cbs if getattr(cb, "before_iteration", False)}
    callbacks_after = cbs - callbacks_before
    callbacks_before = sorted(callbacks_before, key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted(callbacks_after, key=lambda cb: getattr(cb, "order", 0))

    for i in range(num_boost_round):
        for cb in callbacks_before:
            cb(callback_mod.CallbackEnv(model=cvfolds, params=params,
                                        iteration=i, begin_iteration=0,
                                        end_iteration=num_boost_round,
                                        evaluation_result_list=None))
        for b in cvfolds.boosters:
            b.update(fobj=fobj)
        raw = [b.eval_valid(feval) + (b.eval_train(feval) if eval_train_metric else [])
               for b in cvfolds.boosters]
        raw = [[(n if eval_train_metric else n, m, v, bb) for n, m, v, bb in r]
               for r in raw]
        res = _agg_cv_result(raw, eval_train_metric)
        for _, key, mean, _, std in res:
            results[f"{key}-mean"].append(mean)
            results[f"{key}-stdv"].append(std)
        try:
            for cb in callbacks_after:
                cb(callback_mod.CallbackEnv(model=cvfolds, params=params,
                                            iteration=i, begin_iteration=0,
                                            end_iteration=num_boost_round,
                                            evaluation_result_list=res))
        except callback_mod.EarlyStopException as e:
            cvfolds.best_iteration = e.best_iteration + 1
            for bst in cvfolds.boosters:
                bst.best_iteration = cvfolds.best_iteration
            for k in results:
                results[k] = results[k][:cvfolds.best_iteration]
            break
    out = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvfolds
    return out
