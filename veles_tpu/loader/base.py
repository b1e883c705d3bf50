"""Loader base: the minibatch-serving contract.

Equivalent of the reference's veles/loader/base.py:72-1181 (``Loader``):
three sample sets served per epoch in the fixed order TEST → VALIDATION →
TRAIN, per-epoch train shuffling, label statistics, epoch/end flags, and
static-size minibatches (the reference zero-padded short tails,
veles/loader/base.py:749-753 — here padding comes with a validity mask so
jitted steps keep static shapes and padded samples are inert).

The reference's distributed index-serving plane (master sends indices,
slave fills data locally, :631-663) is superseded by SPMD: every host runs
the same loader with the same seed and takes its shard of each minibatch
(see parallel/). ``failed_minibatches`` re-serving maps to checkpoint
restart."""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy

from ..config import root
from ..error import NoMoreJobs
from ..memory import Array
from ..mutable import Bool
from ..units import Unit
from .. import prng

TEST, VALID, TRAIN = 0, 1, 2
CLASS_NAMES = ("test", "validation", "train")


class Loader(Unit):
    """Minibatch server (reference: veles/loader/base.py:120)."""

    hide_from_registry = True

    def __init__(self, workflow, minibatch_size=100, shuffle_limit=None,
                 shard_dataset=False, prefetch_depth=None, **kwargs):
        super().__init__(workflow, **kwargs)
        self.view_group = "LOADER"
        self.max_minibatch_size = int(minibatch_size)
        #: data-plane prefetch (overlap engine, veles_tpu/overlap/
        #: prefetch.py): with depth N > 0 the pure per-batch gather
        #: (``fetch_batch``) for up to N upcoming minibatches runs on a
        #: background thread while the current step computes. The
        #: serving state machine — offsets, epoch flags, PRNG shuffles
        #: — stays on the main thread, so results are bit-identical
        #: with prefetch on or off (the producer walks a frozen copy of
        #: this epoch's index order and never crosses an epoch
        #: boundary). Host-fill path only; fused/plan modes already
        #: overlap via async dispatch (their host work is index
        #: bookkeeping, not sample gathering).
        if prefetch_depth is None:
            prefetch_depth = root.common.overlap.get(
                "prefetch_depth", 0) or 0
        self.prefetch_depth = int(prefetch_depth)
        self._prefetcher = None
        #: None = not probed yet; False = this loader has no pure
        #: fetch_batch (custom fill) — prefetch silently falls back
        self._prefetch_supported: Optional[bool] = None
        #: shard the device-resident dataset over the mesh 'data' axis
        #: instead of replicating it on every chip: HBM per chip scales
        #: 1/n with the axis (GSPMD turns the in-step gather into the
        #: needed collectives). Keep False for small datasets — the
        #: replicated gather is collective-free.
        self.shard_dataset = bool(shard_dataset)
        #: samples per class: [test, validation, train]
        self.class_lengths: List[int] = [0, 0, 0]
        self.epoch_number = 0
        #: unlimited shuffles by default (reference shuffle_limit)
        self.shuffle_limit = (numpy.inf if shuffle_limit is None
                              else shuffle_limit)
        #: train on a random subset of the train class (ensemble members,
        #: reference --ensemble-train N:r, veles/ensemble/base_workflow.py:59)
        self.train_ratio = 1.0
        # flags (reference :862-878)
        self.epoch_ended = Bool(False)
        self.last_minibatch = Bool(False)
        self.train_ended = Bool(False)
        self.test_ended = Bool(False)
        # per-minibatch outputs
        self.minibatch_data = Array(name=self.name + ".minibatch_data")
        self.minibatch_labels = Array(name=self.name + ".minibatch_labels")
        self.minibatch_indices = Array(name=self.name + ".minibatch_indices")
        self.minibatch_mask = Array(name=self.name + ".minibatch_mask")
        self.minibatch_class = TRAIN
        self.minibatch_size = 0          # valid samples in this minibatch
        self.minibatch_offset = 0
        #: serve N minibatches per run() as a (N, mb) index plan — the
        #: fused TrainStep scans over them in ONE device dispatch (kills
        #: per-step dispatch latency)
        self.plan_steps = 1
        #: number of valid rows in the current plan
        self.plan_length = 1
        #: when True, a fused step consumes indices on device and the host
        #: minibatch_data fill is skipped entirely
        self.fused = False
        #: serve H whole epochs per run() as per-class (H, K_c, mb) index
        #: plans (TrainStep epochs_per_dispatch: ONE device dispatch
        #: covers H epochs of eval+train — the per-epoch host round trip
        #: disappears). Set by TrainStep; fused-only.
        self.block_epochs = 1
        #: {class: (idx Array (H, K_c, mb) int32, mask Array f32)} —
        #: allocated on first serve_epoch_block
        self.block_plans: Dict[int, tuple] = {}
        #: hard epoch cap (Decision.max_epochs, set by StandardWorkflow):
        #: the FINAL block clamps to the epochs remaining under it —
        #: training past max_epochs would desynchronize the reported
        #: trajectory from the actual weights
        self.block_epochs_cap: Optional[int] = None
        #: epochs actually served by the last serve_epoch_block
        self.block_length = 0
        self._global_offset = 0
        self._shuffled_indices: Optional[numpy.ndarray] = None
        self.samples_served = 0
        # label bookkeeping (reference label mapping/stats :120-…)
        self.labels_mapping: Dict[object, int] = {}
        self.prng = prng.get(self.name)

    # -- subclass contract ---------------------------------------------------
    def load_data(self) -> None:
        """Populate class_lengths (+ dataset storage). Called at init."""
        raise NotImplementedError

    def create_minibatch_data(self) -> None:
        """Allocate minibatch_data/labels arrays with static shapes."""
        raise NotImplementedError

    def fill_minibatch(self) -> None:
        """Copy samples minibatch_indices → minibatch_data/labels."""
        raise NotImplementedError

    # -- prefetch seam (overlap engine) --------------------------------------
    def fetch_batch(self, idx, size):
        """PURE gather of one minibatch: given an index row, return
        {name → ndarray} for the ``minibatch_<name>`` arrays — or None
        when this loader cannot gather outside its own state (custom
        fill/augmentation). Must be thread-safe (runs on the prefetch
        producer thread) and must not touch serving state or PRNG.
        Subclasses with a pure fill implement it (FullBatchLoader)."""
        return None

    def apply_batch(self, batch) -> None:
        """Install a :meth:`fetch_batch` result into the minibatch
        arrays (main thread — the one place prefetch writes shared
        state)."""
        for name, arr in batch.items():
            getattr(self, "minibatch_" + name).map_invalidate()[...] = arr

    # -- derived geometry ----------------------------------------------------
    @property
    def total_samples(self) -> int:
        return int(sum(self.class_lengths))

    @property
    def class_end_offsets(self) -> List[int]:
        ends, acc = [], 0
        for n in self.class_lengths:
            acc += n
            ends.append(acc)
        return ends

    def class_of_offset(self, offset: int) -> int:
        for idx, end in enumerate(self.class_end_offsets):
            if offset < end:
                return idx
        raise NoMoreJobs("offset %d beyond %d samples" %
                         (offset, self.total_samples))

    # -- lifecycle -----------------------------------------------------------
    def initialize(self, **kwargs):
        res = super().initialize(**kwargs)
        if res:
            return res
        self.load_data()
        if self.total_samples == 0:
            raise NoMoreJobs("loader %s has no samples" % self.name)
        # BEFORE train_ratio subsetting: the check must see the labels the
        # class_lengths geometry still describes
        self.check_label_diversity()
        self._shuffled_indices = numpy.arange(self.total_samples,
                                              dtype=numpy.int32)
        if self.train_ratio < 1.0 and self.class_lengths[TRAIN]:
            # random train subset: keep head (test+valid) intact, replace
            # the train tail with a sampled subset of itself
            start = self.class_end_offsets[VALID]
            train = self._shuffled_indices[start:]
            keep = max(1, int(round(len(train) * self.train_ratio)))
            subset = self.prng.permutation(len(train))[:keep] + start
            self._shuffled_indices = numpy.concatenate(
                [self._shuffled_indices[:start],
                 subset.astype(numpy.int32)])
            self.class_lengths[TRAIN] = keep
        self.shuffle()
        self.create_minibatch_data()
        n = self.max_minibatch_size
        if self.plan_steps > 1:
            # the plan height is STATIC: the fused consumer scans every
            # row, and rows past a class/epoch boundary are mask-zero
            # DEAD COMPUTE. Clamp to the tallest per-class height so a
            # large minibatch cannot silently burn most of the dispatch
            # on masked rows (measured: the mb=256 conv-AE at the
            # default 16-step plan spent 12/16 rows masked — 4x the
            # work per served sample of the mb=64 config)
            tallest = max((self.plan_rows_for(c) for c in range(3)
                           if self.class_lengths[c]), default=1)
            if tallest < self.plan_steps:
                # say so: a silently overridden steps_per_dispatch is a
                # mystery to whoever configured it (ADVICE)
                self.info("%s: plan_steps clamped %d -> %d (tallest "
                          "class plan)", self.name, self.plan_steps,
                          tallest)
                self.plan_steps = tallest
        k = self.plan_steps
        if k > 1 and not self.fused:
            from ..error import Bug
            raise Bug("plan_steps>1 requires a fused consumer (host "
                      "fill_minibatch cannot batch plans)")
        shape = (k, n) if k > 1 else (n,)
        self.minibatch_indices.reset(numpy.zeros(shape, dtype=numpy.int32))
        self.minibatch_mask.reset(numpy.zeros(shape, dtype=numpy.float32))
        self.info(
            "%s: %d samples (test=%d validation=%d train=%d), mb=%d",
            self.name, self.total_samples, *self.class_lengths, n)
        return None

    def check_label_diversity(self) -> Optional[float]:
        """χ² homogeneity check of VALIDATION vs TRAIN label distributions
        (reference: veles/loader/base.py:1007): a skewed split usually
        means a broken loader. Warns; returns the p-value (None when not
        applicable)."""
        labels = getattr(self, "original_labels", None)
        if labels is None:
            return None
        if hasattr(labels, "mem"):      # veles_tpu Array
            labels = labels.mem
        if labels is None:              # Array allocated but empty
            return None
        labels = numpy.asarray(labels).ravel()
        if labels.size == 0:
            return None
        try:        # optional dep, like lmdb/h5py: diagnostic only —
            # probe before doing any counting work
            from scipy.stats import chi2 as chi2_dist
        except ImportError:
            return None
        offs = self.class_end_offsets
        valid = labels[offs[TEST]:offs[VALID]]
        train = labels[offs[VALID]:offs[TRAIN]]
        if len(valid) == 0 or len(train) == 0:
            return None
        classes = numpy.union1d(numpy.unique(valid), numpy.unique(train))
        if len(classes) < 2:
            return None
        cv = numpy.array([(valid == c).sum() for c in classes], float)
        ct = numpy.array([(train == c).sum() for c in classes], float)
        # χ² two-sample homogeneity statistic
        n1, n2 = cv.sum(), ct.sum()
        pooled = (cv + ct) / (n1 + n2)
        expected_v, expected_t = pooled * n1, pooled * n2
        with numpy.errstate(divide="ignore", invalid="ignore"):
            chi2 = numpy.nansum((cv - expected_v) ** 2 / expected_v +
                                (ct - expected_t) ** 2 / expected_t)
        p = float(chi2_dist.sf(chi2, df=len(classes) - 1))
        if p < 0.01:
            self.warning(
                "%s: validation/train label distributions differ "
                "(χ²=%.1f, p=%.2g) — check the dataset split",
                self.name, chi2, p)
        return p

    def shuffle(self) -> None:
        """Shuffle ONLY the train tail (reference: veles/loader/base.py
        shuffles train indices each epoch)."""
        if self.class_lengths[TRAIN] == 0:
            return
        if self.epoch_number > self.shuffle_limit:
            return
        start = self.class_end_offsets[VALID]
        train = self._shuffled_indices[start:]
        self.prng.shuffle(train)

    # -- the serving loop ----------------------------------------------------
    def run(self) -> None:
        from ..resilience.faults import fire as fire_fault
        fire_fault("loader.batch")
        if self.block_epochs > 1:
            self.serve_epoch_block()
        elif self.plan_steps > 1:
            self.serve_plan()
        else:
            self.serve_next_minibatch()

    def _begin_serving(self) -> None:
        if bool(self.epoch_ended):
            # previous run ended the epoch: start a new one
            self.epoch_number += 1
            self._global_offset = 0
            self.shuffle()
        self.epoch_ended <<= False
        self.last_minibatch <<= False
        self.train_ended <<= False
        self.test_ended <<= False

    def _geometry_for(self, offset):
        """(class, valid size) of the minibatch at ``offset`` — pure
        read of the epoch geometry, shared by the serial server and
        the prefetch producer (ONE copy of the walk rule: the two
        paths must never disagree on what batch lives at an offset)."""
        cls = self.class_of_offset(offset)
        return cls, min(self.max_minibatch_size,
                        self.class_end_offsets[cls] - offset)

    def _next_geometry(self):
        """(offset, class, valid_size) of the next minibatch."""
        offset = self._global_offset
        cls, size = self._geometry_for(offset)
        return offset, cls, size

    def _fill_row(self, idx_row, mask_row, offset, size,
                  indices=None) -> None:
        """Write one index row (tail-padded with the last valid index)
        and optionally its validity mask. ``indices`` defaults to the
        live shuffle order; the prefetch producer passes its frozen
        per-epoch copy — same pad rule, one implementation."""
        src = self._shuffled_indices if indices is None else indices
        idx_row[:size] = src[offset:offset + size]
        idx_row[size:] = idx_row[size - 1] if size else 0
        if mask_row is not None:
            mask_row[:size] = 1.0
            mask_row[size:] = 0.0

    def _advance(self, cls, size) -> None:
        """Move the global offset and update flags
        (reference: veles/loader/base.py:862-878)."""
        self.samples_served += size
        self._global_offset += size
        if self._global_offset >= self.class_end_offsets[cls]:
            if cls == TEST:
                self.test_ended <<= True
            if cls == TRAIN:
                self.train_ended <<= True
        if self._global_offset >= self.total_samples:
            self.last_minibatch <<= True
            self.epoch_ended <<= True
            self.event("epoch", "single", number=self.epoch_number)

    def serve_next_minibatch(self) -> None:
        """(reference: veles/loader/base.py:726)"""
        self._begin_serving()
        offset, cls, size = self._next_geometry()
        self.minibatch_offset = offset
        self.minibatch_class = cls
        self.minibatch_size = size
        self._fill_row(self.minibatch_indices.map_invalidate(),
                       self.minibatch_mask.map_invalidate(), offset, size)
        if not self.fused:
            if self.prefetch_depth > 0:
                self._fill_prefetched(offset)
            else:
                self.fill_minibatch()
        self._advance(cls, size)

    # -- prefetch machinery (overlap engine, docs/overlap.md) ----------------
    def _epoch_batches(self, start, indices, total):
        """Generator the prefetch producer runs: walk THIS epoch's
        remaining geometry over a frozen index copy, gathering each
        batch with the pure :meth:`fetch_batch`. Geometry and pad rule
        come from the same ``_geometry_for``/``_fill_row`` the serial
        server uses (class_lengths are stable within an epoch). No
        serving state, no PRNG — the main thread replays the identical
        geometry, so prefetch changes when the gather happens, never
        its content."""
        offset = start
        while offset < total:
            cls, size = self._geometry_for(offset)
            idx = numpy.empty(self.max_minibatch_size, numpy.int32)
            self._fill_row(idx, None, offset, size, indices=indices)
            yield {"offset": offset,
                   "batch": self.fetch_batch(idx, size),
                   "last": offset + size >= total}
            offset += size

    def _arm_prefetcher(self):
        """Start a producer for the CURRENT epoch from the CURRENT
        offset (re-armed each epoch — the producer must see the
        post-shuffle order, and must never shuffle itself)."""
        if self._prefetch_supported is False:
            return None
        from ..overlap.prefetch import Prefetcher
        self._prefetcher = Prefetcher(
            self._epoch_batches(
                self._global_offset,
                numpy.array(self._shuffled_indices),
                self.total_samples),
            depth=self.prefetch_depth,
            name="%s.epoch%d" % (self.name, self.epoch_number))
        return self._prefetcher

    def _fill_prefetched(self, offset) -> None:
        """The prefetching variant of ``fill_minibatch()``: install the
        staged batch, or fall back inline when the loader has no pure
        gather or the stream desynced (e.g. mid-epoch resume)."""
        pf = self._prefetcher or self._arm_prefetcher()
        if pf is None:
            self.fill_minibatch()
            return
        try:
            rec = pf.get()
        except StopIteration:
            rec = None
        if rec is not None and rec["batch"] is None:
            # probed unsupported: this loader customizes its fill —
            # permanent inline fallback, said once
            self._prefetch_supported = False
            self._close_prefetcher()
            self.info("%s: fetch_batch not supported — prefetch_depth="
                      "%d falls back to inline fill", self.name,
                      self.prefetch_depth)
            self.fill_minibatch()
            return
        if rec is None or rec["offset"] != offset:
            self._close_prefetcher()
            self.fill_minibatch()
            return
        self._prefetch_supported = True
        self.apply_batch(rec["batch"])
        if rec["last"]:
            # epoch exhausted: the next epoch re-arms AFTER the main
            # thread's shuffle (in _begin_serving order)
            self._close_prefetcher()

    def _close_prefetcher(self) -> None:
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None

    def stop(self) -> None:
        self._close_prefetcher()

    def serve_plan(self) -> None:
        """Serve up to plan_steps minibatches of ONE sample class as a
        (plan_steps, mb) index/mask plan; unused rows are mask-zero.
        Stops early at class or epoch boundaries so Decision/flag semantics
        stay exact."""
        self._begin_serving()
        idx = self.minibatch_indices.map_invalidate()
        mask = self.minibatch_mask.map_invalidate()
        first_cls = None
        k = 0
        while k < self.plan_steps:
            if self._global_offset >= self.total_samples:
                break
            offset, cls, size = self._next_geometry()
            if first_cls is None:
                first_cls = cls
                self.minibatch_offset = offset
            elif cls != first_cls:
                break
            self._fill_row(idx[k], mask[k], offset, size)
            self._advance(cls, size)
            k += 1
        mask[k:] = 0.0
        idx[k:] = 0
        self.minibatch_class = first_cls if first_cls is not None else TRAIN
        self.plan_length = k
        self.minibatch_size = int(mask.sum())
        # no host fill: plan mode is fused-only (enforced at initialize)

    def plan_rows_for(self, cls: int) -> int:
        """Static plan height for one sample class: ceil(len / mb)."""
        n = self.class_lengths[cls]
        mb = self.max_minibatch_size
        return -(-n // mb) if n else 0

    def serve_epoch_block(self) -> None:
        """Serve ``block_epochs`` WHOLE epochs as per-class stacked index
        plans: for each class c with samples, (H, K_c, mb) indices+mask.
        The epoch walk order inside each epoch is the offset order
        (test → validation → train), exactly the classic loop's order;
        flags/counters advance as if the epochs were served one by one,
        so Decision/Snapshotter semantics are unchanged (they just see H
        epochs per drain)."""
        from ..error import Bug
        if not self.fused:
            raise Bug("serve_epoch_block requires a fused consumer")
        h = self.block_epochs
        if self.block_epochs_cap is not None:
            completed = self.epoch_number + (1 if bool(self.epoch_ended)
                                             else 0)
            h = max(1, min(h, self.block_epochs_cap - completed))
        mb = self.max_minibatch_size
        if not self.block_plans:
            for cls in (TEST, VALID, TRAIN):
                rows = self.plan_rows_for(cls)
                if not rows:
                    continue
                shape = (h, rows, mb)
                self.block_plans[cls] = (
                    Array(numpy.zeros(shape, numpy.int32),
                          name="%s.block_idx%d" % (self.name, cls)),
                    Array(numpy.zeros(shape, numpy.float32),
                          name="%s.block_mask%d" % (self.name, cls)))
        self.block_length = h
        views = {cls: (idx.map_invalidate(), mask.map_invalidate())
                 for cls, (idx, mask) in self.block_plans.items()}
        for e in range(h):
            self._begin_serving()
            rows_done = {cls: 0 for cls in views}
            while self._global_offset < self.total_samples:
                offset, cls, size = self._next_geometry()
                idx, mask = views[cls]
                k = rows_done[cls]
                self._fill_row(idx[e, k], mask[e, k], offset, size)
                rows_done[cls] = k + 1
                self._advance(cls, size)
            # epoch_ended is now True; the next e re-enters a new epoch
        self.minibatch_class = TRAIN
        self.plan_length = self.plan_rows_for(TRAIN)
        self.minibatch_size = mb

    # -- checkpoint protocol -------------------------------------------------
    def state_dict(self):
        return {
            "epoch_number": self.epoch_number,
            "global_offset": self._global_offset,
            # train_ratio subsetting rewrites geometry at initialize;
            # a resume in a fresh process (default ratio 1.0) must see
            # the subset geometry the indices were built for
            "class_lengths": list(self.class_lengths),
            "shuffled_indices": (None if self._shuffled_indices is None
                                 else numpy.array(self._shuffled_indices)),
            "samples_served": self.samples_served,
            "flags": {"epoch_ended": bool(self.epoch_ended),
                      "last_minibatch": bool(self.last_minibatch),
                      "train_ended": bool(self.train_ended),
                      "test_ended": bool(self.test_ended)},
        }

    def load_state_dict(self, sd) -> None:
        # a restored position invalidates anything staged ahead; the
        # desync guard in _fill_prefetched would catch it, but closing
        # now avoids serving a whole stale epoch into the fallback path
        self._close_prefetcher()
        self.epoch_number = sd["epoch_number"]
        self._global_offset = sd["global_offset"]
        if "class_lengths" in sd:
            self.class_lengths = list(sd["class_lengths"])
        if sd["shuffled_indices"] is not None:
            self._shuffled_indices = numpy.array(sd["shuffled_indices"])
        self.samples_served = sd["samples_served"]
        flags = sd["flags"]
        self.epoch_ended <<= flags["epoch_ended"]
        self.last_minibatch <<= flags["last_minibatch"]
        self.train_ended <<= flags["train_ended"]
        self.test_ended <<= flags["test_ended"]

    # -- introspection -------------------------------------------------------
    def get_metric_values(self) -> Dict[str, object]:
        return {"epochs_served": self.epoch_number,
                "samples_served": self.samples_served}


class LoaderMSE(Loader):
    """Loader with regression targets instead of integer labels
    (reference: veles/loader/base.py:1149)."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.minibatch_targets = Array(name=self.name + ".minibatch_targets")
