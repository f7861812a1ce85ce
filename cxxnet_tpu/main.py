"""CLI task driver: train / finetune / pred / extract from a config file.

Reference: ``src/cxxnet_main.cpp`` (CXXNetLearnTask).  Usage parity:

    python -m cxxnet_tpu <config.conf> [key=value ...]

Tasks: ``task = train | finetune | pred | pred_raw | extract | serve |
check``; snapshots
``model_dir/%04d.model`` every ``save_model`` rounds; ``continue = 1``
resumes from the newest snapshot (SyncLastestModel, cxxnet_main.cpp:135-157);
``test_io = 1`` runs the loop without Update (I/O benchmark mode, :363-389).
"""

from __future__ import annotations

import os
import re
import sys
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from . import ckpt as ckptlib, engine
from .analysis.schema import K
from .ckpt import CKPT_KEYS
from .serve import SERVE_KEYS
from .io.device_prefetch import DevicePrefetcher, StagedGroup, item_h2d_sec
from .io.factory import create_iterator, init_iterator
from .monitor import TrainingDiverged, log as mlog
from .monitor.spans import HOST_FED_FIELDS, PhaseClock, phase_fields
from .monitor.trace import ProfileWindow
from .nnet.trainer import NetTrainer
from .utils.config import parse_config_file, parse_keyval_args

#: keys LearnTask.set_param consumes — the task half of the config
#: surface (the trainer half is nnet/trainer.TRAINER_KEYS).  Harvested
#: by analysis/registry.py; keep in sync with set_param below.
TASK_KEYS = (
    K("print_step", "int", lo=1),
    K("continue", "int", lo=0, hi=1),
    K("save_model", "int", lo=0),
    K("start_counter", "int", lo=0),
    K("model_in", "path"), K("model_dir", "path"),
    K("num_round", "int", lo=0), K("max_round", "int", lo=0),
    K("silent", "int", lo=0, hi=1),
    K("task", "enum", choices=("train", "finetune", "pred", "pred_raw",
                               "extract", "check", "serve")),
    K("dev", "str"),
    K("test_io", "int", lo=0, hi=1),
    K("multi_step", "int", lo=0),
    K("prefetch_device", "int", lo=0),
    K("synth_device_data", "int", lo=0, hi=1),
    K("extract_node_name", "str"),
    K("eval_train", "int", lo=0, hi=1),
    K("prof", "path"),
    K("prof_start_step", "int", lo=-1),
    K("prof_num_steps", "int", lo=0),
    K("prof_every", "int", lo=0,
      help="recurring profiling windows: trace every Nth round"),
    K("sentinel", "int", lo=0, hi=1,
      help="EWMA regression sentinels over step time / comm_share / "
           "HBM high-water (anomaly records need metrics_sink)"),
    K("sentinel_rel", "float", lo=0.01, hi=10.0,
      help="relative deviation vs the EWMA that fires an anomaly "
           "(must be > 0: a zero threshold fires on every observation)"),
    K("sentinel_warmup", "int", lo=1),
    K("sentinel_ring", "int", lo=1,
      help="flight-recorder depth: last K step records dumped on an "
           "anomaly or TrainingDiverged"),
    # goodput ledger (monitor/ledger.py, doc/monitor.md): end-of-run
    # wall accounting, emitted from the task finally so a diverged run
    # still lands it; tools/obsv.py --diff compares two of them
    K("ledger", "int", lo=0, hi=1,
      help="emit the end-of-run goodput ledger record (default 1; "
           "needs metrics_sink, train/finetune tasks only)"),
    K("test_on_server", "int", lo=0, hi=1),
    # OOM pre-flight (analysis/memmodel.py, doc/memory.md): task=check
    # runs the analytic memory model against the target chip's HBM
    K("mem_check", "int", lo=0, hi=1,
      help="task=check: error when the estimated peak HBM exceeds the "
           "target chip's capacity (warn inside mem_margin_pct)"),
    K("mem_margin_pct", "float", lo=0, hi=90,
      help="pre-flight warning margin: warn when the estimate lands "
           "within this % of capacity (default 10)"),
    K("mem_chip", "str",
      help="pre-flight HBM capacity selector (v4/v5e/v5p/v6e or a "
           "full device_kind); defaults to dev= when it names a chip"),
    # SPMD deep lint (analysis/spmdlint.py, doc/check.md): collective-
    # consistency, donation audit, dtype-flow over the traced step
    K("spmd_check", "int", lo=0, hi=1,
      help="task=check: run the SPMD deep lint (default 1; 0 skips the "
           "collective/donation/dtype-flow pass)"),
    # the runtime deliberately tolerates unknown spellings (treated as
    # binary, with a warning) — soft keeps the lint at warn severity
    K("output_format", "enum", choices=("txt", "bin"), soft=True),
    K("dist_coordinator", "str"),
    K("dist_num_proc", "int", lo=1),
    K("dist_proc_rank", "int", lo=0),
    # serving keys (serve/__init__.py declares them next to their
    # consumer, ServeConfig.from_pairs; doc/serve.md) and checkpoint /
    # rollback keys (ckpt/__init__.py; doc/checkpoint.md)
) + SERVE_KEYS + CKPT_KEYS


class LearnTask:
    def __init__(self):
        self.task = "train"
        self.net_type = 0
        self.print_step = 100
        self.continue_training = 0
        self.save_period = 1
        # reference default 0 (cxxnet_main.cpp:27): the pre-training
        # snapshot is 0000.model and rounds 1..num_round then train —
        # starting at 1 would silently train one round fewer
        self.start_counter = 0
        self.name_model_in = "NULL"
        self.name_model_dir = "./"
        self.num_round = 10
        self.max_round = 2147483647
        self.silent = 0
        self.test_io = 0
        self.multi_step = 0
        # device-side input prefetch (doc/io.md): a producer thread stages
        # batches (stack/cast/sharded device_put/input_s2d) this many
        # dispatches ahead of the train loop, so H2D transfer overlaps
        # device compute.  0 = stage synchronously (still off the
        # dispatch timer)
        self.prefetch_device = 2
        self._eval_prefetchers: Optional[list] = None
        self._pred_prefetcher = None
        # diagnostic twin of test_io: test_io=1 isolates the input
        # pipeline (no device work); synth_device_data=1 isolates the
        # device loop (pre-staged on-device batches, no host transfer)
        self.synth_device_data = 0
        self.extract_node_name = ""
        self.prof_dir = ""
        # generalized profiling window (doc/monitor.md): start the trace
        # before global update step prof_start_step and run prof_num_steps
        # dispatches (0 = to round end).  The default -1 keeps the legacy
        # window — the whole round past compilation
        self.prof_start_step = -1
        self.prof_num_steps = 0
        # prof_every = N: recurring low-overhead profiling windows — a
        # fresh trace (and its trace/layer_profile records) every Nth
        # round instead of the single one-shot window (doc/monitor.md)
        self.prof_every = 0
        # regression sentinels + flight recorder (monitor/sentinel.py)
        self.sentinel = 0
        self.sentinel_rel = 0.2
        self.sentinel_warmup = 3
        self.sentinel_ring = 64
        self._sentinel_bank = None
        # goodput ledger (doc/monitor.md): fold the run's own records
        # into an end-of-run wall-accounting record from run()'s finally
        self.ledger = 1
        self._run_t0: Optional[float] = None
        # the sink appends: bytes already in the file at run start are
        # an earlier session's and must not fold into THIS run's ledger
        self._sink_offset = 0
        # fault-tolerant checkpoints (doc/checkpoint.md): ckpt_async=1
        # snapshots at round boundaries into atomic NNNN.ckpt dirs off
        # the training thread; save_opt carries optimizer state (exact
        # resume); ckpt_iter_state carries the train-iterator chain
        # state; ckpt_keep bounds retention; rollback=N auto-restores
        # the last good snapshot on TrainingDiverged and retries
        self.ckpt_async = 0
        self.ckpt_keep = 3
        self.rollback = 0
        self.save_opt = 1
        self.ckpt_iter_state = 1
        self._ckpt_writer = None
        self._ckpt_blocked_sec: dict = {}
        # guards _ckpt_blocked_sec: the train thread writes entries
        # around submit() while _ckpt_done pops them on the writer thread
        self._ckpt_lock = threading.Lock()
        self._resume_iter_state = None
        self._resume_sentinel_state = None
        self._warned_iter_capture = False
        # the mem_profile table (monitor/memory.py) is the executable's
        # static truth — built once per trainer, re-emitted per window
        self._mem_profile_cache = None
        # wall seconds of the first train dispatch (jit trace + compile
        # happen synchronously inside it); None until it ran
        self.compile_sec: Optional[float] = None
        self.test_on_server = 0
        self.name_pred = "pred.txt"
        self.output_format = 1
        # default 1, reference nnet_impl-inl.hpp:22; gates both metric
        # accumulation (NetTrainer) and the train metric line below
        self.eval_train = 1
        self.device = "tpu"
        self.cfg: List[Tuple[str, str]] = []
        self.net: Optional[NetTrainer] = None
        self.itr_train = None
        self.itr_evals = []
        self.eval_names = []
        # racelint: atomic(whole-object swap published by init_data before the serve producer thread starts; the producer only reads)
        self.itr_pred = None

    def set_param(self, name: str, val: str) -> None:
        if val == "default":
            return
        if name == "print_step":
            self.print_step = int(val)
        elif name == "continue":
            self.continue_training = int(val)
        elif name == "save_model":
            self.save_period = int(val)
        elif name == "start_counter":
            self.start_counter = int(val)
        elif name == "model_in":
            self.name_model_in = val
        elif name == "model_dir":
            self.name_model_dir = val
        elif name == "num_round":
            self.num_round = int(val)
        elif name == "max_round":
            self.max_round = int(val)
        elif name == "silent":
            self.silent = int(val)
            mlog.set_silent(self.silent)
        elif name == "task":
            self.task = val
        elif name == "dev":
            self.device = val
        elif name == "test_io":
            self.test_io = int(val)
        elif name == "multi_step":
            self.multi_step = int(val)
        elif name == "prefetch_device":
            self.prefetch_device = int(val)
        elif name == "synth_device_data":
            self.synth_device_data = int(val)
        elif name == "extract_node_name":
            self.extract_node_name = val
        elif name == "eval_train":
            self.eval_train = int(val)
        elif name == "prof":
            self.prof_dir = val
        elif name == "prof_start_step":
            self.prof_start_step = int(val)
        elif name == "prof_num_steps":
            self.prof_num_steps = int(val)
        elif name == "prof_every":
            self.prof_every = int(val)
        elif name == "sentinel":
            self.sentinel = int(val)
        elif name == "sentinel_rel":
            self.sentinel_rel = float(val)
        elif name == "sentinel_warmup":
            self.sentinel_warmup = int(val)
        elif name == "sentinel_ring":
            self.sentinel_ring = int(val)
        elif name == "ledger":
            self.ledger = int(val)
        elif name == "ckpt_async":
            self.ckpt_async = int(val)
        elif name == "ckpt_keep":
            self.ckpt_keep = max(int(val), 1)
        elif name == "rollback":
            self.rollback = int(val)
        elif name == "save_opt":
            self.save_opt = int(val)
        elif name == "ckpt_iter_state":
            self.ckpt_iter_state = int(val)
        elif name == "test_on_server":
            self.test_on_server = int(val)
        elif name == "output_format":
            # Reference (cxxnet_main.cpp:100-102) treats anything non-"txt"
            # as binary; keep that contract but warn on unknown spellings.
            if val not in ("txt", "bin"):
                mlog.warn(f"output_format={val!r} not 'txt'/'bin'; "
                          "treating as binary")
            self.output_format = 1 if val == "txt" else 0
        self.cfg.append((name, val))

    # ----------------------------------------------------------------- init
    def _create_net(self) -> NetTrainer:
        net = NetTrainer()
        for k, v in self.cfg:
            net.set_param(k, v)
        return net

    def _sync_latest_model(self) -> bool:
        """SyncLastestModel (cxxnet_main.cpp:135-157), hardened: scan
        ``model_dir`` for the newest *loadable* snapshot — ``NNNN.ckpt``
        atomic dirs and legacy ``NNNN.model`` files — newest first,
        SKIPPING partial/corrupt ones (a manifest-less or
        checksum-failing dir is what a kill mid-write leaves; the
        previous snapshot is the resume point, and the next save
        overwrites the debris)."""
        cands = [(c, p) for c, p in
                 ckptlib.list_snapshots(self.name_model_dir)
                 if c >= self.start_counter]
        # same finite-params gate as rollback: a rollback that walked
        # past a NaN-poisoned snapshot leaves it on disk (crc-valid,
        # loadable) — a restart must not resume from it either
        return self._restore_newest_valid(
            cands, who="continue",
            reject=self._reject_nonfinite) is not None

    @staticmethod
    def _reject_nonfinite(net):
        """Reject hook for the resume scans: the divergence may predate
        a snapshot, and poisoned params would just diverge again."""
        import jax
        finite = all(bool(np.isfinite(np.asarray(leaf)).all())
                     for leaf in jax.tree.leaves(net.params))
        return None if finite else "carries non-finite params; walking back"

    def _restore_newest_valid(self, cands, who: str, reject=None):
        """Walk ``(counter, path)`` candidates NEWEST-first and restore
        the first loadable one into ``self.net``: partial/corrupt
        ``.ckpt`` dirs (what a kill mid-write leaves) are skipped with a
        warning, torn legacy files are skipped at load, and ``reject``
        — given the loaded trainer, returning a reason string or None —
        lets the rollback path refuse poisoned snapshots.  Shared by
        ``continue = 1`` and rollback so the two resume paths cannot
        drift.  Sets ``start_counter`` past the restored round, stashes
        iterator/sentinel resume state, and returns ``(counter, path)``
        or None."""
        for counter, path in reversed(cands):
            is_ckpt = path.endswith(".ckpt")
            if is_ckpt and ckptlib.validate_snapshot(path) is None:
                # one line per skipped snapshot, bounded candidate list
                mlog.warn(f"{who}: skipping partial/corrupt snapshot "  # disclint: ok(warn-once)
                          f"{path}")
                continue
            net = self._create_net()
            try:
                net.load_model(path, validated=is_ckpt)
            except Exception as e:  # noqa: BLE001 — torn legacy file
                net.metrics.close()
                mlog.warn(f"{who}: snapshot {path} failed to load "  # disclint: ok(warn-once)
                          f"({e}); trying the previous one")
                continue
            why = reject(net) if reject is not None else None
            if why:
                net.metrics.close()
                mlog.warn(f"{who}: snapshot {path} {why}")  # disclint: ok(warn-once)
                continue
            old, self.net = self.net, net
            if old is not None and old is not net:
                old.metrics.close()
            self.start_counter = counter + 1
            self._stash_resume_state(net.loaded_extra)
            return counter, path
        return None

    def _stash_resume_state(self, extra) -> None:
        """Hold a loaded snapshot's iterator / sentinel state until the
        consumers exist (iterators after ``_create_iterators``, the
        sentinel bank inside the train loop)."""
        if not extra:
            return
        if self.ckpt_iter_state:
            self._resume_iter_state = extra.get("iter_state")
        self._resume_sentinel_state = extra.get("sentinel_state")

    def _apply_iter_resume(self) -> None:
        st, self._resume_iter_state = self._resume_iter_state, None
        if st and self.itr_train is not None:
            try:
                self.itr_train.set_state(st)
            except Exception as e:  # noqa: BLE001 — resume best-effort
                mlog.warn(f"iterator state restore failed ({e}); the "
                          "train iterator resumes cold")

    def _maybe_init_distributed(self) -> None:
        """Join the JAX distributed runtime when a coordinator is configured
        (config keys dist_coordinator/dist_num_proc/dist_proc_rank; env vars
        CXN_COORDINATOR/CXN_NUM_PROC/CXN_PROC_RANK override so one config
        file serves every worker, like the reference's dist launcher —
        example/MNIST/mpi.conf, nnet_ps_server.cpp:41-48)."""
        cfg = dict(self.cfg)
        coord = os.environ.get("CXN_COORDINATOR",
                               cfg.get("dist_coordinator", ""))
        if not coord:
            return
        nproc = int(os.environ.get("CXN_NUM_PROC",
                                   cfg.get("dist_num_proc", "1")))
        rank = int(os.environ.get("CXN_PROC_RANK",
                                  cfg.get("dist_proc_rank", "0")))
        from .parallel import mesh as meshlib
        meshlib.init_distributed(coord, nproc, rank)
        # shard the data pipeline by process unless the config did already
        if "dist_num_worker" not in cfg:
            self.set_param("dist_num_worker", str(nproc))
            self.set_param("dist_worker_rank", str(rank))
        mlog.info(f"distributed: rank {rank}/{nproc} via {coord}, "
                  f"{len(__import__('jax').devices())} global devices")

    def init(self) -> None:
        self._maybe_init_distributed()
        if self.task == "train" and self.continue_training:
            if self._sync_latest_model():
                mlog.notice(
                    f"Init: Continue training from round {self.start_counter}")
                self._create_iterators()
                self._apply_iter_resume()
                return
            raise RuntimeError(
                "Init: cannot find models for continue training; "
                "specify model_in instead")
        self.continue_training = 0
        if self.name_model_in == "NULL":
            assert self.task == "train", "must specify model_in if not training"
            self.net = self._create_net()
            self.net.init_model()
        elif self.task == "finetune":
            self.net = self._create_net()
            self.net.init_model()
            self.net.copy_model_from(self.name_model_in)
        else:
            self.net = self._create_net()
            self.net.load_model(self.name_model_in)
            m = re.search(r"(\d+)\.(?:model|ckpt)$", self.name_model_in)
            if m:
                self.start_counter = int(m.group(1)) + 1
        self._create_iterators()

    def _create_iterators(self) -> None:
        """Section scanner (reference CreateIterators, cxxnet_main.cpp:214-264)."""
        if self.synth_device_data:
            return  # device-loop diagnostic: no input pipeline
        flag = 0
        evname = ""
        itcfg: List[Tuple[str, str]] = []
        defcfg: List[Tuple[str, str]] = []
        for name, val in self.cfg:
            if name == "data":
                flag = 1
                continue
            if name == "eval":
                evname = val
                flag = 2
                continue
            if name == "pred":
                flag = 3
                self.name_pred = val
                continue
            if name == "iter" and val == "end":
                assert flag != 0, "wrong configuration file"
                if flag == 1 and self.task != "pred":
                    assert self.itr_train is None, "can only have one data"
                    self.itr_train = create_iterator(itcfg)
                if flag == 2 and self.task != "pred":
                    self.itr_evals.append(create_iterator(itcfg))
                    self.eval_names.append(evname)
                if flag == 3 and self.task in ("pred", "pred_raw",
                                               "extract", "serve"):
                    assert self.itr_pred is None, "can only have one pred data"
                    self.itr_pred = create_iterator(itcfg)
                flag = 0
                itcfg = []
                continue
            (itcfg if flag != 0 else defcfg).append((name, val))
        # input_s2d: emit space-to-depth batches from the host pipeline
        # (the device staging transform is a measured-slow fallback);
        # wrapping happens BEFORE init so a ThreadBufferIterator's
        # producer thread runs the transform in the prefetch overlap
        self.itr_train = self._wrap_s2d(self.itr_train)
        self.itr_evals = [self._wrap_s2d(it) for it in self.itr_evals]
        self.itr_pred = self._wrap_s2d(self.itr_pred)
        for it in ([self.itr_train] if self.itr_train else []) + \
                self.itr_evals + ([self.itr_pred] if self.itr_pred else []):
            init_iterator(it, defcfg)

    def _wrap_s2d(self, it):
        s2d_args = getattr(self.net, "_s2d_args", None) if self.net else None
        if s2d_args is None or it is None:
            return it
        from .io.iter_proc import (DenseBufferIterator, S2DEmitIterator,
                                   ThreadBufferIterator)
        # splice beneath the DEEPEST buffering stage in the chain so the
        # transform runs in the prefetch producer thread (threadbuffer)
        # or once at cache fill (membuffer), not on the consumer path
        deepest = None
        cur = it
        while hasattr(cur, "base") and cur.base is not None:
            if isinstance(cur, (ThreadBufferIterator, DenseBufferIterator)):
                deepest = cur
            cur = cur.base
        if deepest is not None:
            deepest.base = S2DEmitIterator(deepest.base, s2d_args)
            return it
        return S2DEmitIterator(it, s2d_args)

    def _close_prefetchers(self) -> None:
        """Join every device-prefetch producer thread (train src is owned
        by task_train's own finally).  Idempotent — the task methods call
        it from their finally blocks so a mid-round exception
        (TrainingDiverged from ``monitor_nan = fatal``, an iterator
        error) can't leak staging threads past the task, and run() keeps
        it as a backstop for direct task_*() callers."""
        for pf in (self._eval_prefetchers or []) + \
                ([self._pred_prefetcher] if self._pred_prefetcher else []):
            pf.close()
        self._eval_prefetchers = None
        self._pred_prefetcher = None

    def _emit_trace_report(self, prof: ProfileWindow) -> None:
        """Reports from one closed profile window: per-step ``comm_sec``
        / ``overlap_frac`` gauges plus a ``trace`` record (the measured
        collective time the dp_overlap schedule is judged on) and a
        ``layer_profile`` record (per-layer device-time attribution with
        roofline distance, doc/monitor.md).  The window's xplane is
        parsed ONCE and feeds both.  Parse failures must never kill
        training."""
        metrics = self.net.metrics if self.net else None
        if metrics is None:
            return
        tdir = prof.last_window_dir or self.prof_dir
        steps = max(prof.last_window_steps, 1)
        try:
            from .monitor.trace import (comm_report_in, find_xplane,
                                        parse_xspace)
            planes = parse_xspace(find_xplane(tdir))
            rep = comm_report_in(planes, steps=steps)
        except Exception as e:  # noqa: BLE001 — telemetry only
            mlog.warn(f"trace summary of {tdir} failed: {e}")
            return
        metrics.set_gauge("comm_sec", rep["comm_sec"])
        metrics.set_gauge("overlap_frac", rep["overlap_frac"])
        if metrics.active:
            metrics.emit("trace", round=self.start_counter - 1, **rep)
            if self._sentinel_bank is not None:
                self._sentinel_bank.observe_trace(
                    dict(rep, round=self.start_counter - 1))
            self._emit_layer_profile(planes, steps)
            self._emit_mem_profile()

    def _emit_layer_profile(self, planes, steps: int) -> None:
        """Book the window's per-op device self times to layer scope
        and pass (monitor/attribution.py) and join the analytic cost
        model (analysis/costmodel.py); emit one ``layer_profile`` record
        carrying the whole table.  The instruction -> ``op_name`` map is
        the executable the window's trace itself holds (its ``Hlo
        Proto``): no second lowering or compile, and a scanned
        ``update_many`` step is covered like a single one."""
        net = self.net
        metrics = net.metrics
        try:
            from .analysis import costmodel
            from .monitor import attribution
            kind = net.devices[0].device_kind
            table = attribution.layer_table(
                planes, steps=steps,
                costs=costmodel.layer_costs(net.net),
                peak_flops=costmodel.peak_flops(kind),
                peak_bw=costmodel.peak_bw(kind))
            metrics.emit("layer_profile", round=self.start_counter - 1,
                         **table)
            if not mlog.is_silent() and table["rows"]:
                top = ", ".join(
                    f"{r['layer']} {r['device_ms']:.3g} ms"
                    for r in table["rows"][:3])
                mlog.info(
                    f"layer_profile: {table['attributed_ms']:.3g} of "
                    f"{table['device_total_ms']:.3g} ms/step attributed "
                    f"({table['coverage'] * 100:.0f}%); top: {top}")
        except Exception as e:  # noqa: BLE001 — telemetry only
            mlog.warn(f"layer attribution failed: {e}")

    def _emit_mem_profile(self) -> None:
        """The memory leg of the observatory (doc/memory.md): join the
        compiled step's buffer liveness (monitor/memory.py) against the
        trainer's placed param/opt trees and the analytic memory model
        (analysis/memmodel.py); emit one ``mem_profile`` record per
        closed profile window.  The HLO parse and the liveness walk are
        cached per trainer — recurring ``prof_every`` windows re-scan
        nothing — and the whole path rides the same cached AOT compile
        ``step_hlo_text`` already paid for layer attribution."""
        net = self.net
        metrics = net.metrics
        try:
            table = self._mem_profile_cache \
                if getattr(self, "_mem_profile_cache", None) is not None \
                else self._build_mem_profile()
            if table is None:
                return
            self._mem_profile_cache = table
            # measured gauges land fresh each window (the cached table
            # is the executable's static truth; the gauges are not)
            gauges = net.memory_gauges()
            table = dict(table, **gauges)
            metrics.emit("mem_profile", round=self.start_counter - 1,
                         **table)
            if not mlog.is_silent() and table["rows"]:
                top = ", ".join(
                    f"{r['layer']} {r['total_bytes'] / 1e6:.2f} MB"
                    for r in table["rows"][:3])
                mlog.info(
                    f"mem_profile: peak live "
                    f"{table['peak_live_bytes'] / 1e6:.2f} MB temps at "
                    f"{table['peak_frac']:.0%} of the step; top: {top}")
            # satellite (doc/monitor.md): on backends without
            # memory_stats() the HBM sentinel can never see a gauge —
            # the executable-derived temp total is its fallback
            # BASELINE.  The cached value is constant per executable
            # (so it cannot fire mid-run by itself); its worth is the
            # series it lands in the sink and the EWMA it seeds, which
            # a RESUMED run's first differing executable is judged
            # against (ckpt carries sentinel state)
            bank = self._sentinel_bank
            if bank is not None and not gauges:
                exec_stats = table.get("exec") or {}
                fb = exec_stats.get("temp_bytes") \
                    or table["peak_live_bytes"]
                if fb:
                    bank.observe_round({"round": self.start_counter - 1,
                                        "hbm_peak_bytes": int(fb)})
        except Exception as e:  # noqa: BLE001 — telemetry only
            mlog.warn(f"memory attribution failed: {e}")

    def _build_mem_profile(self):
        from .analysis import costmodel, memmodel
        from .monitor import memory as memlib
        net = self.net
        hlo = net.step_hlo_text()
        if not hlo:
            return None
        model = memmodel.layer_mem(net)
        table = memlib.mem_table(
            hlo, net.layer_scopes(),
            exec_stats=net.step_memory_stats(),
            param_rows=memmodel.param_rows(net),
            # the per-row model join compares like with like: the
            # measured total is param+opt+live-act, so the transient
            # grad term stays out of the per-row model_bytes
            model_rows={s: {k: v for k, v in r.items()
                            if k != "grad_bytes"}
                        for s, r in model.items()})
        table["model"] = memmodel.totals(net, model)
        cap = costmodel.hbm_bytes(net.devices[0].device_kind)
        if cap:
            table["hbm_capacity_bytes"] = int(cap)
        return table

    # ---------------------------------------------------------------- tasks
    def _ckpt_extra_state(self, capture_iter: bool = True) -> dict:
        """Non-trainer resume state riding in the snapshot: the train
        iterator chain's position/rng state (quiescent at a round
        boundary — the epoch's prefetchers have drained) and the
        sentinel EWMA/ring state.  ``capture_iter = False`` for the
        initial round-0 save: a threadbuffer's init()-primed producer is
        still pulling there, so state() would read racing cursors/rng —
        and a fresh iterator resuming cold IS its round-0 state."""
        extra = {}
        if capture_iter and self.ckpt_iter_state \
                and self.itr_train is not None:
            try:
                extra["iter_state"] = self.itr_train.state()
            except Exception as e:  # noqa: BLE001 — snapshot best-effort
                if not self._warned_iter_capture:
                    self._warned_iter_capture = True
                    mlog.warn(f"iterator state capture failed ({e}); "
                              "snapshots resume the iterator cold")
        if self._sentinel_bank is not None:
            extra["sentinel_state"] = self._sentinel_bank.state()
        return extra

    def _ckpt_done(self, stats: dict) -> None:
        """Writer-thread completion hook: the ``ckpt`` record lands as
        soon as the manifest committed, even while the train loop is
        mid-dispatch."""
        metrics = self.net.metrics
        with self._ckpt_lock:
            blocked = self._ckpt_blocked_sec.pop(stats["counter"], 0.0)
        metrics.counter_inc("ckpt_saves")
        metrics.emit("ckpt", round=stats["counter"], path=stats["path"],
                     async_write=1, shards=stats["shards"],
                     bytes=stats["bytes"],
                     write_sec=round(stats["write_sec"], 4),
                     blocked_sec=round(blocked, 4),
                     pruned=stats["pruned"], keep=self.ckpt_keep)
        mlog.info(f"checkpoint {stats['path']}: {stats['bytes']} bytes "
                  f"in {stats['write_sec']:.3f} sec off-thread "
                  f"(loop blocked {blocked:.3f} sec)")

    def _save_model(self, capture_iter: bool = True) -> None:
        if self._ckpt_writer is not None:
            # a writer failure latched since the last save surfaces at
            # the next round boundary, not silently at process exit
            self._ckpt_writer.poll()
        counter = self.start_counter
        self.start_counter += 1
        if self.save_period == 0 or counter % self.save_period != 0:
            return
        os.makedirs(self.name_model_dir, exist_ok=True)
        extra_state = self._ckpt_extra_state(capture_iter)
        metrics = self.net.metrics
        t0 = time.perf_counter()
        if self.ckpt_async:
            # async atomic snapshot: host pull on this thread (the
            # jitted step donates the device buffers), npz + manifest
            # commit + retention on the writer thread.  submit() blocks
            # only when a previous write is still in flight
            # (bounded-queue backpressure) and re-raises any latched
            # writer failure here, in the train loop
            from .ckpt.writer import AsyncCheckpointWriter
            if self._ckpt_writer is None:
                self._ckpt_writer = AsyncCheckpointWriter(
                    on_done=self._ckpt_done, tracer=metrics.tracer)
            shards, meta = self.net.checkpoint_payload(
                with_opt=bool(self.save_opt), extra_state=extra_state)
            path = ckptlib.snapshot_path(self.name_model_dir, counter)
            # stash the host-pull wall BEFORE submit so the completion
            # hook (writer thread) always finds an entry; fold in the
            # backpressure block after, if the record hasn't landed yet
            pull = time.perf_counter() - t0
            with self._ckpt_lock:
                self._ckpt_blocked_sec[counter] = pull
            block = self._ckpt_writer.submit(
                path, shards, meta, counter=counter, keep=self.ckpt_keep)
            with self._ckpt_lock:
                # the record may already have landed (fast writer): then
                # the entry is gone and its blocked_sec missed the submit
                # block — never re-insert, that entry would leak
                if counter in self._ckpt_blocked_sec:
                    self._ckpt_blocked_sec[counter] = pull + block
            # span: what the TRAIN thread actually paid for this
            # snapshot — the D2H host pull plus bounded-queue
            # backpressure (write_sec - this span is the async win)
            tr = metrics.tracer
            if tr is not None and tr.enabled:
                tr.emit("ckpt_blocked", t0, time.perf_counter(),
                        counter=counter)
            return
        # legacy single-file path, now atomic (tmp + os.replace) and
        # carrying opt state + exact-resume state by default
        path = os.path.join(self.name_model_dir, f"{counter:04d}.model")
        self.net.save_model(path, with_opt_state=bool(self.save_opt),
                            extra_state=extra_state)
        wall = time.perf_counter() - t0
        metrics.counter_inc("ckpt_saves")
        metrics.emit("ckpt", round=counter, path=path, async_write=0,
                     shards=1, bytes=os.path.getsize(path),
                     write_sec=round(wall, 4), blocked_sec=round(wall, 4),
                     pruned=0, keep=self.ckpt_keep)

    def task_train(self) -> None:
        """``task = train``: the train loop under the rollback guard.

        ``rollback = N`` closes the fault-tolerance loop: on
        ``TrainingDiverged`` (the ``monitor_nan = fatal`` guard, or any
        sentinel-confirmed NaN that escalates to it) the task restores
        the newest snapshot whose params are finite, reseeds the rng
        stream past the bad window (``NetTrainer.reseed_rng`` — the
        retried rounds draw different randomness, and later snapshots
        carry the folded key so their own resume stays exact), and
        re-enters the loop, up to N times before re-raising."""
        attempt = 0
        try:
            while True:
                try:
                    self._run_train_loop(initial_save=(attempt == 0))
                    break
                except TrainingDiverged as e:
                    if attempt >= self.rollback \
                            or not self._rollback_restore(e, attempt + 1):
                        raise
                    attempt += 1
            if self._ckpt_writer is not None:
                # drain + close on the success path OUTSIDE the finally:
                # a latched writer failure must fail the run (snapshots
                # silently not landing is the worst outcome)
                w, self._ckpt_writer = self._ckpt_writer, None
                w.close()
        finally:
            if self._ckpt_writer is not None:  # exception path: don't
                w, self._ckpt_writer = self._ckpt_writer, None  # mask
                try:
                    w.close()
                except Exception as ce:  # noqa: BLE001
                    mlog.warn(f"checkpoint writer close failed: {ce}")

    def _rollback_restore(self, exc: BaseException, attempt: int) -> bool:
        """Restore the newest loadable snapshot with all-finite params;
        returns False when none exists (the caller re-raises).  Emits a
        ``rollback`` record and resets ``start_counter`` so the loop
        re-enters at the restored round."""
        died_round = self.start_counter
        if self._ckpt_writer is not None:
            # an in-flight write must commit (or fail) before "newest
            # snapshot" means anything.  A latched writer failure
            # re-raises HERE, before any restore work: per the writer's
            # discipline it must fail the run, and retrying would only
            # hit the same latch at the retry's first _save_model poll
            self._ckpt_writer.drain()
        cands = [(c, p) for c, p in
                 ckptlib.list_snapshots(self.name_model_dir)
                 if c < died_round]
        restored = self._restore_newest_valid(
            cands, who="rollback", reject=self._reject_nonfinite)
        if restored is None:
            mlog.warn(f"rollback: no finite snapshot found in "
                      f"{self.name_model_dir}; re-raising")
            return False
        counter, path = restored
        self.net.reseed_rng(attempt)
        self._apply_iter_resume()
        self.net.metrics.counter_inc("rollbacks")
        self.net.metrics.emit(
            "rollback", retry=attempt, max_retry=self.rollback,
            from_round=died_round, restored_round=counter,
            path=path, reason=f"{type(exc).__name__}: {exc}")
        mlog.result(
            f"rollback {attempt}/{self.rollback}: {type(exc).__name__} "
            f"in round {died_round}; restored {path}, reseeded rng, "
            f"resuming from round {self.start_counter}")
        return True

    def _run_train_loop(self, initial_save: bool = True) -> None:
        start = time.time()
        metrics = self.net.metrics
        if initial_save and self.continue_training == 0 \
                and self.name_model_in == "NULL":
            # round-0 save: the iterator chain is NOT quiescent yet (a
            # threadbuffer's producer primed at init() is mid-pull)
            self._save_model(capture_iter=False)
        if self.prof_every > 0 and self.prof_start_step >= 0:
            # lint surfaces this at check time too (doc/check.md):
            # a step-pinned one-shot window and a recurring round
            # cadence can't both own the profiler
            mlog.warn("prof_every ignored: prof_start_step pins a "
                      "one-shot step-addressed window")
            self.prof_every = 0
        # a rollback swaps self.net mid-run: look the trainer up at the call
        prof = ProfileWindow(self.prof_dir,
                             lambda: self.net.wait_for_device(),
                             self.prof_start_step, self.prof_num_steps,
                             every=self.prof_every)
        if self.synth_device_data:
            self._train_synth_device(prof)
            return
        if self.itr_train is None:
            raise RuntimeError(
                "task=train but the config has no 'data = train' iterator "
                "section; add one (see example/MNIST/MNIST.conf) or use the "
                "wrapper API for in-memory data")
        if self.test_io:
            mlog.notice("start I/O test")
        cc = self.max_round
        rounds_done = 0
        if self.sentinel and metrics.active:
            from .monitor.sentinel import SentinelBank
            self._sentinel_bank = SentinelBank(
                metrics, rel=self.sentinel_rel,
                warmup=self.sentinel_warmup, ring=self.sentinel_ring)
            if self._resume_sentinel_state:
                # resumed run continues the pre-kill EWMA baselines
                # instead of re-warming from scratch
                self._sentinel_bank.set_state(self._resume_sentinel_state)
                self._resume_sentinel_state = None
            if not self.net.memory_gauges():
                # the HBM watcher would silently never arm here (no
                # memory_stats() on this backend, e.g. CPU CI) — say so
                # once.  With prof = <dir> the mem_profile path feeds
                # it the compiled step's temp bytes instead: a static
                # baseline series (one value per executable), not a
                # live high-water — it documents the footprint and
                # seeds a resumable EWMA, it cannot catch runtime
                # allocator growth
                mlog.warn(
                    "sentinel: this backend reports no memory_stats(); "
                    "the HBM watcher gets only the executable-derived "
                    "temp-byte baseline from profile windows (set "
                    "prof = <dir>), not a live high-water")
        elif self.sentinel:
            # every sentinel output goes to the sink; armed without one
            # it would only add a per-print-step D2H loss sync (lint
            # surfaces this at check time too — doc/check.md)
            mlog.warn("sentinel=1 without metrics_sink: sentinels "
                      "disarmed")
        bank = self._sentinel_bank
        # legacy window: profile the second round (past compilation) — or
        # the only round when just one will run; prof_start_step >= 0
        # pins the window to an exact global update step instead
        will_run = min(self.num_round - self.start_counter + 1,
                       self.max_round)
        prof_round = 1 if will_run > 1 else 0
        # multi_step > 1 groups K batches into ONE device dispatch
        # (an on-device lax.scan), the TPU equivalent of the
        # reference's ThreadBuffer keeping the GPU queue full
        # (iter_batch_proc-inl.hpp:136-224); train metrics stay exact
        # (outputs come back stacked, one D2H per group)
        # pairtest nets stay on the per-batch path: grouped dispatch
        # would drop their step diagnostics (reference exceedance
        # reporting); monitored nets too (the scan path carries no
        # per-layer norm outputs)
        group_n = self.multi_step if (
            self.multi_step > 1 and self.test_io == 0
            and self.net.update_period == 1
            and not self.net.has_diagnostics
            and not self.net.monitor) else 1
        # staged item source: grouping + np.stack + dtype cast + sharded
        # device_put + input_s2d all happen OFF the dispatch window — on
        # a producer thread running prefetch_device dispatches ahead
        # (the reference's ThreadBuffer moved host decode off the
        # critical path; this moves the H2D transfer too), or inline
        # just before the dispatch timer when prefetch_device = 0
        # the loop's phase clock (monitor/spans.py): every stretch of
        # host time below is entered as a phase, flat, so the step and
        # round records say where the wall went and a profiler trace
        # shows the same spans beside the device planes.  clock.dispatch
        # counts DISPATCHES, as prof_start_step / prof_num_steps do (a
        # multi_step group is one); trainer.sample_counter counts update
        # steps, which diverges from dispatches under grouping
        clock = PhaseClock()
        src = None if self.test_io else DevicePrefetcher(
            self.itr_train, self.net, group_n=group_n,
            depth=self.prefetch_device, metrics=metrics, clock=clock)
        step_mark = round_mark = clock.read()
        try:
            while self.start_counter <= self.num_round and cc > 0:
                cc -= 1
                mlog.info(f"update round {self.start_counter - 1}")
                with clock.phase("round_boundary"):
                    prof.maybe_start_round(rounds_done, prof_round)
                    round_t0 = time.time()
                    sample_counter = 0
                    n_round = 0
                    t_mark = time.time()
                    n_mark = 0
                    depth_sum = depth_n = 0
                    self.net.start_round(self.start_counter)
                    if src is not None:
                        src.before_first()
                    else:
                        self.itr_train.before_first()
                while True:
                    first_dispatch = False
                    if src is None:
                        # test_io = 1: host pipeline only, no staging
                        with clock.phase("input_wait"):
                            batch = self.itr_train.next()
                        if batch is None:
                            break
                        metas = (batch,)
                    else:
                        # books its own input_wait: blocked on the staging
                        # queue, or on the host iterator when nothing
                        # prefetches
                        item = src.next()
                        if item is None:
                            break
                        # measured where the work ran (the producer thread
                        # when prefetching: off the critical path), these
                        # travel with the item
                        clock.book("host_next", src.last_wait_sec)
                        clock.book("h2d", item_h2d_sec(item))
                        if src.async_:
                            depth_sum += src.last_depth
                            depth_n += 1
                        first_dispatch = self.compile_sec is None
                        with clock.phase("record"):
                            prof.maybe_start_step(clock.dispatch)
                        with clock.phase("compile" if first_dispatch
                                         else "enqueue") as enqueued:
                            if isinstance(item, StagedGroup):
                                self._update_group(item)
                                metas = item.meta
                            else:
                                for sb in item:
                                    self.net.update(sb)
                                metas = item
                        if first_dispatch:
                            # jit traces + compiles synchronously inside
                            # the first dispatch: report it separately
                            # and keep it out of the steady-state
                            # examples/sec window and of the step marks
                            self._note_compile(enqueued.seconds)
                            step_mark = clock.read()
                            t_mark, n_mark = time.time(), 0
                        with clock.phase("record"):
                            if prof.after_step():
                                mlog.info("profile trace written to "
                                          f"{prof.last_window_dir}")
                                self._emit_trace_report(prof)
                    for b in metas:
                        sample_counter += 1
                        n_real = b.batch_size - b.num_batch_padd
                        n_round += n_real
                        if not first_dispatch:
                            n_mark += n_real
                        if sample_counter % self.print_step:
                            continue
                        now = time.time()
                        rate = n_mark / max(now - t_mark, 1e-9)
                        # metrics.active alone: the bank only arms with
                        # an active sink, and if the sink dies mid-run
                        # (emit's OSError guard) this also stops paying
                        # the D2H loss sync for records nobody will see
                        recording = metrics.active and self.test_io == 0
                        loss = getattr(self.net, "_last_loss", None) \
                            if recording else None
                        if loss is not None:
                            # the one place the host-fed loop waits for
                            # the device: every step up to here is done
                            # when the loss arrives
                            with clock.phase("device_wait"):
                                loss = float(np.asarray(loss))
                        with clock.phase("record"):
                            diags = self.net.last_diagnostics()
                            if recording:
                                cut, step_mark = clock.cut(step_mark)
                                rec = dict(
                                    round=self.start_counter - 1,
                                    step=sample_counter,
                                    global_step=self.net.sample_counter,
                                    elapsed_sec=round(now - start, 3),
                                    examples_per_sec=round(rate, 1),
                                    wall_sec=round(cut["wall"], 6),
                                    **phase_fields(cut, 6,
                                                   HOST_FED_FIELDS),
                                    staging_depth=round(
                                        depth_sum / depth_n, 2)
                                    if depth_n else 0.0,
                                    loss=loss)
                                bub = getattr(self.net,
                                              "pipe_bubble_frac", 0.0)
                                if bub:
                                    # pipelined step: ledger carves the
                                    # fill/drain share out of dispatch
                                    rec["pipe_bubble_frac"] = round(bub, 4)
                                rec.update(diags)
                                metrics.emit("step", **rec)
                                if bank is not None:
                                    bank.observe_step(rec)
                            t_mark, n_mark = now, 0
                            depth_sum = depth_n = 0
                            mlog.info(
                                f"round {self.start_counter - 1:8d}:"
                                f"[{sample_counter:8d}] {int(now - start)} "
                                f"sec elapsed, {rate:.1f} examples/sec")
                            self._report_diagnostics(diags)
                    clock.dispatch += 1  # the next unit of work
                with clock.phase("round_boundary"):
                    if prof.round_end():
                        mlog.info("profile trace written to "
                                  f"{prof.last_window_dir}")
                        self._emit_trace_report(prof)
                    rounds_done += 1
                    train_wall = time.time() - round_t0
                    if self.test_on_server:
                        # per-round replica consistency check (the
                        # reference's test_on_server weight check,
                        # async_updater-inl.hpp:144-154)
                        drift = self.net.check_weight_consistency()
                        if drift != 0.0:
                            raise RuntimeError(
                                "replica weights diverged (max abs diff "
                                f"{drift})")
                    round_metrics = {}
                    if self.test_io == 0:
                        line = f"[{self.start_counter}]"
                        # only print the train metric when the trainer
                        # actually accumulated it (eval_train also gates
                        # accumulation in NetTrainer.update — a 0 here
                        # would print all-zero metrics)
                        if self.eval_train:
                            line += self.net.train_eval_line("train")
                            round_metrics.update(
                                self.net.train_metric.values("train"))
                        for it, name in zip(self._eval_sources(),
                                            self.eval_names):
                            line += self.net.evaluate(it, name)
                            round_metrics.update(
                                self.net.metric.values(name))
                        mlog.result(line)
                with clock.phase("record"):
                    # the same sums over the round: cut when the record is
                    # built, so the round's own writing and the save that
                    # follows are booked in the next round's
                    cut, round_mark = clock.cut(round_mark)
                    if metrics.active:
                        rec = dict(round=self.start_counter,
                                   wall_sec=round(train_wall, 3),
                                   eval_sec=round(
                                       time.time() - round_t0 - train_wall,
                                       3),
                                   examples=n_round,
                                   examples_per_sec=round(
                                       n_round / max(train_wall, 1e-9), 1),
                                   **phase_fields(cut, 3, HOST_FED_FIELDS),
                                   train_step_traces=metrics.counters.get(
                                       "train_step_traces", 0),
                                   eval_step_traces=metrics.counters.get(
                                       "eval_step_traces", 0),
                                   **round_metrics)
                        if rounds_done == 1 and self.compile_sec is not None:
                            rec["compile_sec"] = round(self.compile_sec, 3)
                        bub = getattr(self.net, "pipe_bubble_frac", 0.0)
                        if bub:
                            rec["pipe_bubble_frac"] = round(bub, 4)
                        rec.update(self.net.memory_gauges())
                        metrics.emit("round", **rec)
                        if bank is not None:
                            bank.observe_round(rec)
                with clock.phase("round_boundary"):
                    self._save_model()
        except BaseException as e:
            # flight recorder: the last K step records — the run's final
            # approach into a TrainingDiverged or any mid-round failure —
            # land in the sink before the raise propagates
            if bank is not None:
                bank.flight_dump(f"{type(e).__name__}: {e}")
            raise
        finally:
            # producer threads must not outlive the task — a mid-round
            # raise (TrainingDiverged, iterator failure) joins the train
            # src AND the per-eval prefetchers here, not at process exit
            if src is not None:
                src.close()
            self._close_prefetchers()
            self._flush_profile(prof)
        mlog.info(f"\nupdating end, {int(time.time() - start)} sec in all")

    def _note_compile(self, seconds: float) -> None:
        """One ``compile`` record: the first dispatch's trace and compile."""
        self.compile_sec = seconds
        self.net.metrics.emit("compile", compile_sec=round(seconds, 3),
                              round=self.start_counter - 1,
                              pallas_sites=self.net.pallas_sites(),
                              loop_saved=self.net.loop_saved(),
                              ssm_sites=self.net.ssm_sites(),
                              moe_sites=self.net.moe_sites(),
                              loss_sites=self.net.loss_sites())
        mlog.info(f"compile: {seconds:.1f} sec (first dispatch, excluded "
                  "from examples/sec)")

    def _flush_profile(self, prof: ProfileWindow) -> None:
        """A window the run never closed: prof_num_steps past the last
        dispatch, test_io=1, or a mid-round raise landing inside an open
        window (TrainingDiverged under prof_every) — flush it so the
        incident window's trace + layer_profile records survive, and the
        profiler never runs into process exit.  Guarded: a flush failure
        must not mask the in-flight exception."""
        if not prof.active:
            return
        try:
            prof.stop()
            mlog.info(f"profile trace written to {prof.last_window_dir} "
                      "(window truncated at training end)")
            self._emit_trace_report(prof)
        except Exception as pe:
            mlog.warn(f"profile window flush failed: {pe}")

    def _train_synth_device(self, prof: ProfileWindow) -> None:
        """synth_device_data=1: run the REAL config-driven train loop on
        pre-staged device-resident synthetic batches — the device-side twin
        of ``test_io=1``.  Isolates the train-loop dispatch overhead from
        the host input pipeline and the host->device link; compare its
        examples/sec to bench.py's pre-staged number to see the CLI loop's
        own cost.  The ``multi_step`` batches are generated ON the device,
        in the model dtype, in the shape the step consumes (the
        ``input_s2d`` staged shape when set) and under the step's batch
        sharding: no host copy of the stack ever exists (at b1024 x 10
        steps that was 6.3 GB of float32) and no staging transform runs.
        One round = one dispatch over the same batches, so the loss a
        round reports is comparable with the previous round's.  The same
        phase clock and profile window as the host-fed loop: a round here
        is one dispatch, so it passes ``round_boundary`` every time."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        net = self.net
        k = max(self.multi_step, 1)
        shape = net.step_input_shape()
        nclass = net.net.node_shapes[net.net.final_node][-1]
        stacked = NamedSharding(net.mesh, P(None, *net.batch_shard.spec))
        kd, kl = jax.random.split(jax.random.PRNGKey(0))
        datas = jax.jit(
            lambda key: jax.random.uniform(
                key, (k,) + shape, jnp.float32).astype(net.dtype),
            out_shardings=stacked)(kd)
        labels = jax.jit(
            lambda key: jax.random.randint(
                key, (k, shape[0], 1), 0, nclass).astype(jnp.float32),
            out_shardings=stacked)(kl)
        start = time.time()
        clock = PhaseClock()
        step_mark = clock.read()
        try:
            while self.start_counter <= self.num_round:
                first_dispatch = self.compile_sec is None
                with clock.phase("round_boundary"):
                    prof.maybe_start_round(clock.dispatch, 1)
                    self.net.start_round(self.start_counter)
                    prof.maybe_start_step(clock.dispatch)
                with clock.phase("compile" if first_dispatch
                                 else "enqueue") as enqueued:
                    losses = net.update_many(datas, labels)
                if first_dispatch:
                    # jit traces + compiles synchronously inside the first
                    # dispatch: reported separately and kept out of
                    # examples/sec and of the step marks, as in the
                    # host-fed loop
                    self._note_compile(enqueued.seconds)
                    step_mark = clock.read()
                with clock.phase("device_wait") as waited:
                    np.asarray(losses)
                with clock.phase("record"):
                    if prof.after_step() or prof.round_end():
                        mlog.info("profile trace written to "
                                  f"{prof.last_window_dir}")
                        self._emit_trace_report(prof)
                    cut, step_mark = clock.cut(step_mark)
                    rec = dict(round=self.start_counter - 1, step=k,
                               global_step=net.sample_counter,
                               synth_device=1,
                               wall_sec=round(cut["wall"], 6),
                               **phase_fields(cut, 6),
                               loss=float(np.asarray(losses[-1])))
                    if not first_dispatch:
                        rate = shape[0] * k \
                            / (enqueued.seconds + waited.seconds)
                        mlog.info(f"round {self.start_counter - 1:8d}: "
                                  f"synth-device {k} steps, {rate:.1f} "
                                  "examples/sec")
                        rec["examples_per_sec"] = round(rate, 1)
                    rec.update(net.last_diagnostics())
                    net.metrics.emit("step", **rec)
                with clock.phase("round_boundary"):
                    self._save_model()
                clock.dispatch += 1  # the next unit of work
        finally:
            self._flush_profile(prof)
        mlog.info(f"\nupdating end, {int(time.time() - start)} sec in all")

    def _update_group(self, staged: StagedGroup) -> None:
        """Dispatch one staged multi-step group (a device-resident
        ``(k, batch, ...)`` stack — the ``np.stack`` + cast + transfer
        already ran off the dispatch window, on the prefetch producer
        thread or inline via ``NetTrainer.stage_group``) as one on-device
        scan, accumulating the train metric from the stacked eval
        outputs."""
        net = self.net
        want_outs = bool(net.eval_train and net.train_metric.evals)
        if want_outs:
            _, outs = net.update_many(staged.datas, staged.labels,
                                      with_outs=True)
            outs = {nid: np.asarray(v) for nid, v in outs.items()}
            for j, m in enumerate(staged.meta):
                net.accumulate_train_metric(
                    {nid: outs[nid][j] for nid in outs}, m.label)
        else:
            net.update_many(staged.datas, staged.labels)

    def _eval_sources(self):
        """Eval iterators, wrapped with device prefetchers (grouped to
        ``eval_group``, staged ``prefetch_device`` dispatches ahead) when
        prefetching is on; created once and reused every round."""
        if self.prefetch_device <= 0 or self.net is None:
            return self.itr_evals
        if self._eval_prefetchers is None:
            self._eval_prefetchers = [
                DevicePrefetcher(it, self.net,
                                 group_n=self.net.eval_group,
                                 depth=self.prefetch_device,
                                 metrics=self.net.metrics, for_eval=True)
                for it in self.itr_evals]
        return self._eval_prefetchers

    def _pred_source(self):
        """The pred iterator, staged one batch per item ahead of the
        inference loop when prefetching is on."""
        if self.prefetch_device <= 0 or self.itr_pred is None:
            return self.itr_pred
        if self._pred_prefetcher is None:
            self._pred_prefetcher = DevicePrefetcher(
                self.itr_pred, self.net, group_n=1,
                depth=self.prefetch_device, metrics=self.net.metrics,
                for_eval=True)
        return self._pred_prefetcher

    def _report_diagnostics(self, diags) -> None:
        """Print step diagnostics (pairtest fwd/bwd/weight relative errors),
        flagging values over the reference's 1e-5 threshold the way the
        reference prints exceedances to stderr
        (pairtest_layer-inl.hpp:190-196)."""
        if not diags:
            return
        from .layers.pairtest import PAIRTEST_RTOL
        parts, bad = [], []
        for k in sorted(diags):
            v = diags[k]
            if isinstance(v, list):  # a value a pass (exit_loss)
                parts.append(f"{k}=[" + " ".join(f"{x:.3g}" for x in v) + "]")
                continue
            parts.append(f"{k}={v:.3g}")
            if k.endswith("_rel_err") and not v <= PAIRTEST_RTOL:
                bad.append(f"{k}: err={v:g} exceeds {PAIRTEST_RTOL:g}")
        mlog.info("diag: " + " ".join(parts))
        for b in bad:  # one line per exceeded pairtest diag, bounded
            mlog.warn(b)  # disclint: ok(warn-once)

    def task_check(self) -> int:
        """``task = check``: static config lint + traced-graph lint.

        Runs in seconds with no device work and no data files: the
        config lint walks the declared-key registry, the jaxpr lint
        abstract-traces the configured step on CPU (skipped when the
        config has no netconfig block, e.g. pred-from-checkpoint).
        Exit code 1 iff any error-severity finding — a typo'd key fails
        the run *before* a compile-and-train cycle is spent on it."""
        from .analysis import run_check
        path = getattr(self, "_conf_path", "")
        findings, code = run_check(self.cfg, path=path, trace=True)
        counts = {"error": 0, "warn": 0, "info": 0}
        for f in findings:
            counts[f.severity] = counts.get(f.severity, 0) + 1
            emit = mlog.result if f.severity in ("error", "warn") \
                else mlog.info
            emit("check: " + f.format())
        mlog.result(
            f"check: {path or '<config>'}: {counts['error']} error(s), "
            f"{counts['warn']} warning(s), {counts['info']} info")
        # `check` record to the JSONL metrics sink (doc/monitor.md) so
        # CI lint results land in the same stream as train telemetry
        from .monitor.metrics import MetricsRegistry
        reg = MetricsRegistry()
        for k, v in self.cfg:
            if k == "metrics_sink":
                reg.configure_sink(v)
        reg.emit("check", config=path, n_error=counts["error"],
                 n_warn=counts["warn"], n_info=counts["info"],
                 findings=[f.to_dict() for f in findings])
        reg.close()
        return code

    def _observe_latency(self, op: str, sec: float) -> None:
        """Per-batch inference latency into the registry histogram —
        the p50/p95/p99 the serving path (ROADMAP item 1) is judged
        on."""
        self.net.metrics.observe(f"{op}_latency_sec", sec)

    def _emit_latency_record(self, op: str) -> None:
        """One ``latency`` record per pred/extract task: count + mean +
        percentiles of the per-batch dispatch+D2H wall (doc/monitor.md)."""
        metrics = self.net.metrics
        h = metrics.histograms.get(f"{op}_latency_sec")
        if h is None or not h.count:
            return
        s = h.summary()
        metrics.emit("latency", op=op, count=int(s["count"]),
                     **{k: round(s[k] * 1e3, 3)
                        for k in ("mean", "min", "max",
                                  "p50", "p95", "p99")},
                     unit="ms")

    def task_predict(self) -> None:
        assert self.itr_pred is not None, \
            "must specify a pred iterator to generate predictions"
        mlog.notice("start predicting...")
        src = self._pred_source()
        try:
            # disclint: ok(atomic-write) — streamed product rows
            with open(self.name_pred, "w") as fo:
                src.before_first()
                while True:
                    batch = src.next()
                    if batch is None:
                        break
                    t0 = time.perf_counter()
                    pred = self.net.predict(batch)
                    self._observe_latency("pred",
                                          time.perf_counter() - t0)
                    for v in pred:
                        fo.write(f"{v:g}\n")
            self._emit_latency_record("pred")
        finally:
            self._close_prefetchers()
        mlog.notice(f"finished prediction, write into {self.name_pred}")

    def task_predict_raw(self) -> None:
        """task=pred_raw: write full output rows (e.g. softmax probabilities)
        space-separated, one instance per line (reference
        cxxnet_main.cpp TaskPredictRaw)."""
        assert self.itr_pred is not None, \
            "must specify a pred iterator to generate predictions"
        mlog.notice("start predicting raw scores...")
        src = self._pred_source()
        try:
            # disclint: ok(atomic-write) — streamed product rows
            with open(self.name_pred, "w") as fo:
                src.before_first()
                while True:
                    batch = src.next()
                    if batch is None:
                        break
                    t0 = time.perf_counter()
                    out = self.net.predict_raw(batch)
                    self._observe_latency("pred",
                                          time.perf_counter() - t0)
                    for row in out:
                        fo.write(" ".join(f"{v:g}" for v in row) + "\n")
            self._emit_latency_record("pred")
        finally:
            self._close_prefetchers()
        mlog.notice(f"finished prediction, write into {self.name_pred}")

    def task_extract(self) -> None:
        assert self.itr_pred is not None, \
            "must specify a pred iterator for feature extraction"
        node = self.extract_node_name
        assert node, "must set extract_node_name"
        mlog.notice(f"start extracting feature from node {node} ...")
        binary = self.output_format == 0
        src = self._pred_source()
        try:
            with open(self.name_pred, "wb" if binary else "w") as fo:
                src.before_first()
                wrote_meta = False
                while True:
                    batch = src.next()
                    if batch is None:
                        break
                    t0 = time.perf_counter()
                    feat = self.net.extract_feature(batch, node)
                    self._observe_latency("extract",
                                          time.perf_counter() - t0)
                    if not wrote_meta:
                        with open(self.name_pred + ".meta", "w") as fm:  # disclint: ok(atomic-write)
                            fm.write(f"{feat.shape[1]}\n")
                        wrote_meta = True
                    if binary:
                        # raw little-endian float32 rows (reference
                        # cxxnet_main.cpp:316 fwrite path)
                        fo.write(np.ascontiguousarray(
                            feat, dtype="<f4").tobytes())
                    else:
                        for row in feat:
                            fo.write(" ".join(f"{v:g}" for v in row) + "\n")
            self._emit_latency_record("extract")
        finally:
            self._close_prefetchers()
        mlog.notice(f"finished extraction, write into {self.name_pred}")

    def task_serve_gen(self, cfg) -> None:
        """``task = serve`` + ``serve_gen = 1``: autoregressive
        generation through the KV-cache incremental-decode engine with
        token-level continuous batching (serve/decode.py, doc/serve.md
        "Incremental decode").  Each valid pred-iterator row's leading
        ``serve_gen_prompt`` token ids become one generation request;
        ``serve_clients`` threads submit them concurrently and the step
        scheduler keeps the ``decode_slots`` batch full.  Generated ids
        land in ``name_pred`` (space-separated per request); the run
        emits per-token + per-request ``latency`` records and one
        ``serve_gen`` record (tokens/sec, occupancy histogram, retrace
        count — the telemetry ``bench.py --lm-serve`` sweeps)."""
        from .serve.host import GenModel, ModelHost, load_draft_trainer
        metrics = self.net.metrics
        draft = None
        if cfg.spec_k >= 1 and not cfg.draft_model:
            raise ValueError(
                f"spec_k = {cfg.spec_k} without serve_draft_model: "
                "speculation needs a draft snapshot (doc/serve.md)")
        if cfg.draft_model:
            if cfg.spec_k >= 1:
                mlog.notice(
                    f"serve: loading draft model {cfg.draft_model} "
                    f"(speculative decoding, spec_k = {cfg.spec_k})")
                draft = load_draft_trainer(self.cfg, cfg.draft_model)
            else:
                mlog.warn("serve: serve_draft_model set but spec_k = 0 "
                          "— speculation stays off")
        gm = GenModel(self.net, cfg, draft_trainer=draft,
                      metrics=metrics)
        # admin plane (serve/admin.py): same lifecycle as task_serve —
        # endpoint up before warmup (503 /readyz through compilation),
        # ready only once both decode executables are pinned.  The
        # generation path has no sentinel reporter, so /statusz shows
        # live scheduler counters without a last-window row and the
        # SLO keys ride only the classic serve path (doc/serve.md)
        host = ModelHost()
        host.attach(gm, warmup=False)
        admin = None
        if cfg.admin_port:
            import dataclasses as _dc
            admin = host.start_admin(metrics, port=cfg.admin_port,
                                     config=_dc.asdict(cfg))
        n_exec = 2 + len(gm.engine.block_widths) \
            + (2 if gm.draft is not None else 0)
        mlog.notice(
            f"serve: warming decode engine ({cfg.slots} slot(s), "
            f"max_seqlen {gm.engine.max_seqlen}, {n_exec} "
            "executables) ...")
        gm.warmup()
        mlog.info(f"serve: decode warmup compiled in "
                  f"{gm.engine.warmup_sec:.1f} sec")
        if not host.mark_ready():
            mlog.warn("serve: host failed the ready admission check")
        footprint = gm.footprint()
        if footprint:
            metrics.set_gauge("serve_footprint_bytes",
                              footprint["total_bytes"])
            mlog.info(
                f"serve: decode footprint "
                f"{footprint['total_bytes'] / 1e6:.1f} MB/device "
                f"(KV cache {footprint['kv_cache_bytes'] / 1e6:.2f} MB "
                f"over {cfg.slots} slot(s))")
        import queue as _queue
        import threading
        results: dict = {}
        errors: List[BaseException] = []
        abort = threading.Event()
        work: "_queue.Queue" = _queue.Queue(maxsize=cfg.queue_depth)
        _DONE = object()
        n_total = [0]

        def _put(item) -> bool:
            while not abort.is_set():
                try:
                    work.put(item, timeout=0.05)
                    return True
                except _queue.Full:
                    continue
            return False

        def producer():
            try:
                self.itr_pred.before_first()
                idx = 0
                while True:
                    batch = self.itr_pred.next()
                    if batch is None:
                        break
                    valid = np.array(
                        batch.data[:batch.batch_size
                                   - batch.num_batch_padd], np.float32)
                    rows = valid.reshape(valid.shape[0], -1)
                    for i in range(rows.shape[0]):
                        prompt = rows[i, :cfg.gen_prompt].astype(np.int32)
                        if not _put((idx, prompt)):
                            return
                        idx += 1
                n_total[0] = idx
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)
                abort.set()
            finally:
                for _ in range(cfg.clients):
                    if not _put(_DONE):
                        return

        def client():
            while True:
                try:
                    item = work.get(timeout=0.05)
                except _queue.Empty:
                    if abort.is_set():
                        return
                    continue
                if item is _DONE:
                    return
                i, prompt = item
                try:
                    results[i] = gm.generate(prompt)
                except BaseException as e:  # noqa: BLE001 — reported
                    errors.append(e)
                    abort.set()
                    return

        mlog.notice(f"serve: streaming generation over {cfg.clients} "
                    "client thread(s)")
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, daemon=True,
                                    name=f"cxxnet-serve-gen-{j}")
                   for j in range(cfg.clients)]
        prod = threading.Thread(target=producer, daemon=True,
                                name="cxxnet-serve-gen-producer")
        try:
            prod.start()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            prod.join()
            dur = time.perf_counter() - t0
            if errors:
                raise errors[0]
            # disclint: ok(atomic-write) — streamed product rows
            with open(self.name_pred, "w") as fo:
                for i in range(n_total[0]):
                    fo.write(" ".join(str(t) for t in results[i]) + "\n")
            self._emit_latency_record("token")
            self._emit_latency_record("gen")
            metrics.set_gauge("serve_retraces", gm.retraces)
            stats = gm.scheduler.stats()
            tps = stats["tokens"] / max(dur, 1e-9)
            if metrics.active:
                metrics.emit(
                    "serve_gen", model=gm.name,
                    duration_sec=round(dur, 3),
                    tokens_per_sec=round(tps, 1),
                    slots=cfg.slots, max_seqlen=gm.engine.max_seqlen,
                    gen_tokens=cfg.gen_tokens, clients=cfg.clients,
                    sample=cfg.gen_sample, retraces=gm.retraces,
                    **stats,
                    **({"footprint": footprint} if footprint else {}))
            if gm.retraces:
                mlog.warn(f"serve: {gm.retraces} decode retrace(s) past "
                          "warmup — a shape escaped the pinned "
                          "executable set (engine bug)")
            spec_note = (
                f", acceptance {stats['acceptance_rate']:.0%} over "
                f"{stats['verify_calls']} verify dispatch(es)"
                if "acceptance_rate" in stats else "")
            mlog.result(
                f"serve: generated {stats['tokens']} tokens for "
                f"{n_total[0]} requests in {dur:.2f} sec "
                f"({tps:.1f} tok/s, mean occupancy "
                f"{stats['mean_occupancy']}, "
                f"{stats['batching']} batching{spec_note}), "
                f"retraces {gm.retraces}")
        finally:
            host.close()   # not-ready first, scheduler drain, admin join
        mlog.notice(f"finished serving, wrote {self.name_pred}")

    def task_serve(self) -> None:
        """``task = serve``: host the loaded model behind the dynamic
        micro-batching predict engine and replay the ``pred`` iterator
        as a concurrent request stream — ``serve_clients`` threads each
        submitting single-row requests, the batcher coalescing them into
        shape-bucket dispatches (doc/serve.md).  Predictions land in
        ``name_pred`` exactly like ``task = pred``; the run emits the
        serving telemetry the observatory reads (one ``latency`` record
        with p50/p95/p99, a ``serve`` record with QPS / batch-size
        histogram / queue-depth stats, and the retrace gauge).
        ``serve_gen = 1`` routes to :meth:`task_serve_gen` — KV-cache
        incremental decode for LM netconfigs."""
        assert self.itr_pred is not None, (
            "task=serve requires a 'pred = <out>' iterator section "
            "(the request stream)")
        from .serve import ServeConfig
        from .serve.host import ServeModel
        cfg = ServeConfig.from_pairs(self.cfg)
        if cfg.gen:
            return self.task_serve_gen(cfg)
        metrics = self.net.metrics
        sm = ServeModel(self.net, cfg, metrics=metrics)
        # live control plane (serve/admin.py, doc/serve.md "Operating a
        # serve host"): the host carries the ready lifecycle and owns
        # the admin endpoint, which starts BEFORE warmup so /readyz
        # reads 503 while executables compile — the hot-swap admission
        # window a poller must see as not-yet-ready
        from .serve.host import ModelHost
        host = ModelHost()
        host.attach(sm, warmup=False)
        admin = None
        if cfg.admin_port:
            import dataclasses as _dc
            admin = host.start_admin(metrics, port=cfg.admin_port,
                                     config=_dc.asdict(cfg))
        mlog.notice(
            f"serve: warming {len(cfg.shapes)} shape bucket(s) "
            f"{list(cfg.shapes)}, dtype={cfg.dtype} ...")
        sm.warmup()
        mlog.info(f"serve: warmup compiled in {sm.engine.warmup_sec:.1f} "
                  "sec")
        # per-model executable footprint (doc/memory.md): what this
        # model costs the device pool resident — the serve record
        # carries it so a multi-model host can pack against capacity
        # instead of packing blind
        footprint = sm.footprint()
        if footprint:
            metrics.set_gauge("serve_footprint_bytes",
                              footprint["total_bytes"])
            mlog.info(
                f"serve: model footprint "
                f"{footprint['total_bytes'] / 1e6:.1f} MB/device "
                f"(weights {footprint['weight_bytes'] / 1e6:.1f} MB + "
                f"{footprint['buckets']} bucket executable(s))")
        # quantization pairtest on real request data (doc/serve.md):
        # the measured side of the declared SERVE_TOL envelope, run on
        # the first serve_calib batches before serving starts
        if cfg.dtype != "f32" and cfg.calib > 0:
            calib_rows: List[np.ndarray] = []
            self.itr_pred.before_first()
            while len(calib_rows) < cfg.calib:
                batch = self.itr_pred.next()
                if batch is None:
                    break
                calib_rows.append(np.array(
                    batch.data[:batch.batch_size - batch.num_batch_padd],
                    np.float32))
            if calib_rows:
                err = max(sm.engine.pairtest(r) for r in calib_rows)
                metrics.set_gauge("serve_quant_rel_err", err)
                from .serve.engine import SERVE_TOL
                mlog.result(
                    f"serve: {cfg.dtype} pairtest vs f32 on "
                    f"{len(calib_rows)} calibration batch(es): max rel "
                    f"err {err:.3g} (envelope {SERVE_TOL[cfg.dtype]:g})")
        # serve-side regression sentinels (doc/serve.md): a reporter
        # thread samples the batcher's window stats every
        # serve_sentinel_window seconds, emits one serve_window record,
        # and feeds the SentinelBank's serve watchers (p99 rise / QPS
        # drop / queue-depth rise) — the serving-regression signal the
        # hot-swap/rollback machinery (ROADMAP item 4) consumes
        bank = None
        sentinel_stop = None
        sentinel_thread = None
        if cfg.sentinel:
            if not metrics.active:
                mlog.warn("serve_sentinel = 1 without an active "
                          "metrics_sink: serve_window/anomaly records "
                          "have nowhere to land; sentinels disarmed")
            else:
                from .monitor.sentinel import SentinelBank
                bank = SentinelBank(metrics, rel=self.sentinel_rel,
                                    warmup=self.sentinel_warmup,
                                    ring=self.sentinel_ring)
                sm.batcher.track_window = True
        # SLO burn-rate alerting (monitor/slo.py) + anomaly-triggered
        # flight capture (serve/admin.py) ride the sentinel reporter's
        # serve_window stream: the batcher counts per-window budget
        # violations, the tracker evaluates fast/slow burn windows,
        # and either a burn or a sentinel anomaly arms ONE flight —
        # trace_sample boosted for the next serve_flight_requests
        # requests, then a serve_flight record with the window ring
        # and the captured trace_id range
        slo = None
        flight = None
        if bank is not None:
            from .serve.admin import FlightCapture
            flight = FlightCapture(
                metrics, lambda: sm.batcher.n_requests, model=sm.name,
                boost=cfg.flight_boost, requests=cfg.flight_requests,
                stats_fn=sm.batcher.stats)
            bank.on_anomaly = lambda hit: flight.trigger(
                f"anomaly: {hit['metric']} {hit['direction']} "
                f"{hit['rel_dev']:+.0%}")
            if cfg.slo_p99_ms > 0.0:
                from .monitor.slo import SloSpec, SloTracker
                sm.batcher.slo_ms = cfg.slo_p99_ms
                slo = SloTracker(
                    SloSpec(p99_ms=cfg.slo_p99_ms, avail=cfg.slo_avail,
                            fast_sec=cfg.slo_fast_sec,
                            slow_sec=cfg.slo_slow_sec,
                            fast_burn=cfg.slo_fast_burn,
                            slow_burn=cfg.slo_slow_burn),
                    cfg.sentinel_window, metrics=metrics,
                    model=sm.name,
                    on_burn=lambda rec: flight.trigger(
                        f"slo: {rec['tier']} burn {rec['burn']:.1f} "
                        f">= {rec['threshold']:g}"))
        elif cfg.slo_p99_ms > 0.0:
            mlog.warn("serve_slo_p99_ms without serve_sentinel = 1 "
                      "(and an active metrics_sink): the SLO evaluates "
                      "over the sentinel reporter's serve_window "
                      "stream; targets ignored")
        if admin is not None:
            admin.slo = slo
            admin.flight = flight
            # even without sentinels, the reporter feeds /statusz its
            # last-window QPS/p99 — scraping needs the window stream
            sm.batcher.track_window = True
        # stream the request iterator: each VALID row of each pred batch
        # becomes one single-row request (round_batch padding excluded,
        # like predict_raw) fed through a BOUNDED work queue — the
        # batcher, not the file layout, decides the dispatch batching,
        # and host memory stays O(queue), not O(dataset) (task=pred's
        # streaming discipline)
        mlog.notice(f"serve: streaming requests over {cfg.clients} "
                    "client thread(s)")
        import queue as _queue
        import threading
        results: dict = {}          # idx -> raw output rows
        errors: List[BaseException] = []
        abort = threading.Event()
        work: "_queue.Queue" = _queue.Queue(
            maxsize=max(cfg.queue_depth, 2 * cfg.max_batch))
        _DONE = object()
        n_total = [0]

        def _put(item) -> bool:
            while not abort.is_set():
                try:
                    work.put(item, timeout=0.05)
                    return True
                except _queue.Full:
                    continue
            return False

        def producer():
            try:
                self.itr_pred.before_first()
                idx = 0
                while True:
                    batch = self.itr_pred.next()
                    if batch is None:
                        break
                    valid = np.array(
                        batch.data[:batch.batch_size
                                   - batch.num_batch_padd], np.float32)
                    for i in range(valid.shape[0]):
                        if not _put((idx, valid[i:i + 1])):
                            return
                        idx += 1
                n_total[0] = idx
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)
                abort.set()
            finally:
                for _ in range(cfg.clients):
                    if not _put(_DONE):
                        return

        def client():
            while True:
                try:
                    item = work.get(timeout=0.05)
                except _queue.Empty:
                    if abort.is_set():
                        return
                    continue
                if item is _DONE:
                    return
                i, row = item
                try:
                    results[i] = sm.predict(row)
                except BaseException as e:  # noqa: BLE001 — reported below
                    errors.append(e)
                    abort.set()
                    return

        def reporter(stop_evt):
            win = 0
            last_t = time.perf_counter()

            def tick():
                nonlocal win, last_t
                ws = sm.batcher.window_stats()
                now = time.perf_counter()
                # qps over the ACTUAL elapsed window, not the nominal
                # one: the tail tick at stop covers a partial window,
                # and dividing by the full width would deflate qps and
                # fire a spurious drop anomaly on every clean shutdown
                dt, last_t = max(now - last_t, 1e-6), now
                win += 1
                rec = {"model": sm.name, "window": win,
                       "window_sec": round(dt, 3),
                       "requests": ws["requests"],
                       "qps": round(ws["requests"] / dt, 2),
                       "queue_depth": ws["queue_depth"]}
                if "viol" in ws:
                    rec["viol"] = ws["viol"]
                for k in ("p50_ms", "p95_ms", "p99_ms"):
                    if k in ws:
                        rec[k] = ws[k]
                metrics.emit("serve_window", **rec)
                # the admin plane caches the window for /statusz (and
                # the flight ring) via whole-object swaps — the scrape
                # path reads it without ever touching this thread's
                # locks
                if admin is not None:
                    admin.note_window(sm.name, rec)
                elif flight is not None:
                    flight.note_window(rec)
                # every window feeds the bank: an idle one (requests=0,
                # so qps/p99 are falsy and skipped inside observe_serve)
                # still drives the queue-depth watcher — a dispatcher
                # stall grows the queue while NOTHING completes, the
                # exact window the depth sentinel exists for
                if bank is not None:
                    bank.observe_serve(rec)
                if slo is not None:
                    slo.observe(rec)
                if flight is not None:
                    flight.tick()

            try:
                while not stop_evt.wait(cfg.sentinel_window):
                    tick()
                # drain the tail window at stop so a run shorter than
                # one window still lands its serving stats
                tick()
            except BaseException as e:  # noqa: BLE001 — must surface
                # telemetry must not kill serving, but a silently dead
                # sentinel is worse than none (thread-exc contract)
                mlog.warn(f"serve sentinel reporter died: {e!r}; "
                          "serve_window records stop here")

        # admission: every executable pinned, calibration done, zero
        # retraces — /readyz flips 200 here and a poller may now route
        if not host.mark_ready():
            mlog.warn("serve: host failed the ready admission check")
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, daemon=True,
                                    name=f"cxxnet-serve-client-{j}")
                   for j in range(cfg.clients)]
        prod = threading.Thread(target=producer, daemon=True,
                                name="cxxnet-serve-producer")
        if bank is not None or admin is not None:
            # the reporter drives sentinels AND the admin plane's
            # last-window cache; either consumer starts it
            sentinel_stop = threading.Event()
            sentinel_thread = threading.Thread(
                target=reporter, args=(sentinel_stop,), daemon=True,
                name="cxxnet-serve-sentinel")
            sentinel_thread.start()
        try:
            prod.start()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            prod.join()
            dur = time.perf_counter() - t0
            if errors:
                if bank is not None:
                    bank.flight_dump("serve aborted: " + repr(errors[0]))
                raise errors[0]
            # disclint: ok(atomic-write) — streamed product rows
            with open(self.name_pred, "w") as fo:
                for i in range(n_total[0]):
                    row = results[i][0]
                    v = float(row.argmax()) if row.shape[0] > 1 \
                        else float(row[0])
                    fo.write(f"{v:g}\n")
            self._emit_latency_record("serve")
            metrics.set_gauge("serve_retraces", sm.retraces)
            stats = sm.batcher.stats()
            qps = n_total[0] / max(dur, 1e-9)
            if metrics.active:
                metrics.emit(
                    "serve", model=sm.name, duration_sec=round(dur, 3),
                    qps=round(qps, 1), dtype=cfg.dtype,
                    shapes=list(cfg.shapes), clients=cfg.clients,
                    retraces=sm.retraces,
                    **stats,
                    **({"footprint": footprint} if footprint else {}),
                    **({"quant_rel_err": metrics.gauges[
                        "serve_quant_rel_err"]}
                       if "serve_quant_rel_err" in metrics.gauges else {}))
            if sm.retraces:
                mlog.warn(f"serve: {sm.retraces} retrace(s) past warmup "
                          "— a request shape escaped the declared "
                          "buckets (serve_shapes)")
            mlog.result(
                f"serve: {n_total[0]} requests in {dur:.2f} sec "
                f"({qps:.1f} req/s), {stats['batches']} dispatches "
                f"(mean batch {stats['mean_batch']}), retraces "
                f"{sm.retraces}")
            if bank is not None and bank.anomalies:
                mlog.warn(f"serve: {len(bank.anomalies)} sentinel "
                          "anomaly(ies) — see the anomaly records "
                          "(tools/obsv.py)")
        finally:
            if sentinel_stop is not None:
                sentinel_stop.set()
                sentinel_thread.join()
            # host.close() flips /readyz to 503 BEFORE the batcher
            # drains, then joins the admin endpoint last
            host.close()
        mlog.notice(f"finished serving, wrote {self.name_pred}")

    def _emit_ledger(self) -> None:
        """End-of-run goodput ledger (monitor/ledger.py): re-read the
        run's own sink file (flushed per record, so everything the run
        emitted — including a TrainingDiverged flight dump — is on
        disk) and fold it into one ``ledger`` record.  Called from
        run()'s finally BEFORE the sink closes, so a diverged run still
        lands its ledger; the same fold recomputes post-hoc in
        ``tools/obsv.py`` for historical JSONLs that lack one."""
        if not self.ledger or self.task not in ("train", "finetune"):
            return
        net = self.net
        if net is None or not net.metrics.active or self._run_t0 is None:
            return
        try:
            from .monitor import ledger as ledgerlib
            recs = ledgerlib.load_records(net.metrics.sink.path,
                                          who="ledger",
                                          offset=self._sink_offset)
            led = ledgerlib.build_ledger(
                recs, wall_sec=time.perf_counter() - self._run_t0)
            if led is None:
                return
            net.metrics.emit("ledger", **led)
            mlog.info("ledger: " + ledgerlib.format_ledger(led))
        except Exception as e:  # noqa: BLE001 — telemetry only
            mlog.warn(f"ledger emit failed: {e}")

    def run(self, argv: List[str]) -> int:
        if len(argv) < 1:
            mlog.notice("Usage: python -m cxxnet_tpu <config> [key=value ...]")
            return 0
        # ledger wall starts here: init, iterator construction, and
        # compile are all part of the run the ledger accounts for
        self._run_t0 = time.perf_counter()
        for k, v in parse_config_file(argv[0]):
            self.set_param(k, v)
        for k, v in parse_keyval_args(argv[1:]):
            self.set_param(k, v)
        self._conf_path = argv[0]
        # anchor the ledger at the sink's current size: the JSONL sink
        # appends, so a reused path still carries earlier sessions —
        # even ones killed before their own ledger record could bound
        # them (build_ledger's last-ledger slice covers the clean case)
        spec = dict(self.cfg).get("metrics_sink", "")
        if spec.startswith("jsonl:"):
            sink_path = spec[len("jsonl:"):]
            try:
                self._sink_offset = os.path.getsize(sink_path)
            except OSError:
                self._sink_offset = 0
        if self.task == "check":
            # lint-only: no iterators, no device, no data files
            return self.task_check()
        engine.enable_compile_cache(self.device)
        try:
            self.init()
            mlog.info("initializing end, start working")
            if self.task in ("train", "finetune"):
                self.task_train()
            elif self.task == "pred":
                self.task_predict()
            elif self.task == "pred_raw":
                self.task_predict_raw()
            elif self.task == "extract":
                self.task_extract()
            elif self.task == "serve":
                self.task_serve()
            else:
                raise ValueError(f"unknown task {self.task!r}")
        finally:
            # each close guarded: the broken iterator that aborted the
            # task often fails its close() too, and that must neither
            # mask the original exception nor starve the closes after it
            try:
                self._close_prefetchers()  # backstop; tasks close own
            except Exception as ce:
                mlog.warn(f"prefetcher close failed: {ce}")
            for it in ([self.itr_train] if self.itr_train else []) + \
                    self.itr_evals + ([self.itr_pred] if self.itr_pred else []):
                try:
                    it.close()
                except Exception as ce:
                    mlog.warn(f"iterator close failed: {ce}")  # disclint: ok(warn-once)
            # task-level sink teardown: flush+close HERE, after the
            # task's own emits (flight dumps, trace reports, latency
            # records) ran — a TrainingDiverged or mid-round iterator
            # failure must still land its final records and must not
            # leak the descriptor past the task (the PR-4 prefetcher
            # leak class, applied to telemetry).  The goodput ledger is
            # the run's LAST record: it folds everything above it,
            # including the exception path's flight dump
            self._emit_ledger()  # guards its own failures
            if self.net is not None:
                self.net.metrics.close()
        return 0


def main() -> int:
    return LearnTask().run(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
