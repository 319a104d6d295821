"""Device mesh + distributed tree learners (data / feature / voting).

TPU-native equivalent of the reference's distributed tree learners and
Network layer (reference: src/treelearner/data_parallel_tree_learner.cpp,
feature_parallel_tree_learner.cpp, voting_parallel_tree_learner.cpp;
src/network/network.cpp). The mapping (SURVEY.md §2.3):

- machine list / sockets / MPI  ->  ``jax.sharding.Mesh`` over a 1-D
  ``data`` axis; XLA owns routing over ICI/DCN, no topology maps.
- the reference's 4x3 learner-type x device matrix collapses to ONE
  builder (learner.build_tree_partitioned) parameterized by a ``Comm``
  strategy (learner.Comm):
  * data-parallel: rows sharded, per-leaf histograms psum'd, every shard
    derives the same split (histogram ReduceScatter + best-split argmax
    sync fold into one collective, data_parallel_tree_learner.cpp:155-251).
    Comm per split round: one (3, G, Bp) f32 allreduce of the smaller
    child's histogram.
  * feature-parallel: rows replicated, the split SEARCH is sharded by
    feature ownership and only the winning SplitInfo is argmax-synced
    (feature_parallel_tree_learner.cpp:40-84; SyncUpGlobalBestSplit,
    parallel_tree_learner.h:191). Comm per round: O(B) — one SplitInfo.
  * voting-parallel: rows sharded, histograms stay LOCAL; shards vote
    their top-k features, the global top-2k features' histograms are
    merged and searched (voting_parallel_tree_learner.cpp:151
    GlobalVoting / PV-Tree). Comm per round: O(F) vote counts +
    O(2*top_k * Bm * 3) merged rows — bounded as F grows.
- rank row-partition (pre_partition)  ->  row sharding of the binned
  matrix: ``NamedSharding(mesh, P('data'))``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..dataset import BinnedDataset
from ..learner import Comm, SerialTreeLearner, TreeLog
from ..obs import track_jit
from ..utils.log import Log

DATA_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (DATA_AXIS,))


def round_up(n: int, d: int) -> int:
    return ((n + d - 1) // d) * d


def _tree_log_specs(row_spec: P) -> TreeLog:
    return TreeLog(
        num_splits=P(), split_leaf=P(), feature=P(), bin=P(), kind=P(),
        default_left=P(), gain=P(), left_sum=P(), right_sum=P(),
        go_left=P(), miss_bin=P(), movable=P(), leaf_value=P(),
        leaf_sum=P(), row_leaf=row_spec)


class _MeshTreeLearner(SerialTreeLearner):
    """Shared shard_map wiring for the distributed learners."""

    comm_mode = "data"
    rows_sharded = True

    def __init__(self, config: Config, dataset: BinnedDataset,
                 mesh: Mesh) -> None:
        self.mesh = mesh
        super().__init__(config, dataset, comm_axis=DATA_AXIS)
        n = dataset.num_data
        d = mesh.devices.size
        self.row_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self.rep_sharding = NamedSharding(mesh, P())
        if self.rows_sharded:
            shard = getattr(dataset, "shard_info", None)
            if shard is not None and jax.process_count() > 1:
                # distributed loading: every process holds only its row
                # shard; assemble the global sharded array without any host
                # ever materializing the full matrix (reference analog: the
                # per-rank partitions of dataset_loader.cpp:951)
                rank, world, n_total = shard
                if world != jax.process_count():
                    Log.fatal("dataset was sharded for %d processes but "
                              "%d are running", world, jax.process_count())
                self.padded_n = round_up(n_total, d)
                local = np.asarray(dataset.binned)
                per_proc = self.padded_n // world
                if len(local) != per_proc:
                    pad_rows = per_proc - len(local)
                    if pad_rows < 0:
                        Log.fatal("shard %d has %d rows > %d per-process "
                                  "capacity", rank, len(local), per_proc)
                    local = np.pad(local, ((0, pad_rows), (0, 0)))
                self.bins = jax.make_array_from_process_local_data(
                    self.row_sharding, local)
            else:
                self.padded_n = round_up(n, d)
                bins_np = np.asarray(dataset.binned)
                if self.padded_n != n:
                    bins_np = np.pad(bins_np,
                                     ((0, self.padded_n - n), (0, 0)))
                self.bins = jax.device_put(jnp.asarray(bins_np),
                                           self.row_sharding)
        else:
            self.padded_n = n
            self.bins = jax.device_put(self.bins, self.rep_sharding)

        if self.comm_mode != "data" and not self.use_partition():
            Log.fatal("tree_learner=%s requires the partitioned builder "
                      "(max_bin <= 256)", self.comm_mode)
        self._build = track_jit("mesh/build",
                                jax.jit(self.sharded_build(mesh)))

    def sharded_build(self, mesh: Mesh):
        """The tree builder shard_map'd over ``mesh`` (normally the
        learner's own; the AOT pre-flight re-wraps it over a TPU topology's
        mesh). Replication checking off: the learners do their own
        collectives through Comm."""
        spec = P(DATA_AXIS) if self.rows_sharded else P()
        return jax.shard_map(
            self.make_build_fn(), mesh=mesh,
            in_specs=(spec, spec, P(), P(), P(), P()),
            out_specs=_tree_log_specs(spec), check_vma=False)

    def _make_comm(self, axis: Optional[str]) -> Comm:
        return Comm(axis, mode=self.comm_mode,
                    top_k=int(self.config.top_k),
                    num_machines=int(self.mesh.devices.size),
                    hist_scatter=bool(self.config.tpu_hist_scatter))

    def train(self, ghc: jax.Array, feature_mask: jax.Array, key: jax.Array,
              cegb_used=None) -> TreeLog:
        n = self.dataset.num_data
        if cegb_used is None:
            cegb_used = jnp.zeros((self.dataset.num_features,), bool)
        shard = getattr(self.dataset, "shard_info", None)
        multiproc = self.rows_sharded and shard is not None \
            and jax.process_count() > 1
        if multiproc:
            # the dataset holds only this process's rows: gradients must be
            # assembled the same way the bins were — each process
            # contributes its LOCAL rows to the global row-sharded array
            # (device_put would instead scatter the local array as if it
            # were the global one, pairing rank>0 bins with garbage)
            per_proc = self.padded_n // shard[1]
            loc = np.asarray(ghc)
            if len(loc) != per_proc:
                loc = np.pad(loc, ((0, per_proc - len(loc)), (0, 0)))
            ghc = jax.make_array_from_process_local_data(
                self.row_sharding, loc)
        elif self.rows_sharded and self.padded_n != n:
            ghc = jnp.pad(ghc, ((0, self.padded_n - n), (0, 0)))
        sharding = self.row_sharding if self.rows_sharded else self.rep_sharding
        if not multiproc:
            ghc = jax.device_put(ghc, sharding)
        log = self._build(self.bins, ghc, self.meta, feature_mask, key,
                          cegb_used)
        if multiproc:
            # row_leaf comes back globally sharded; this process's score
            # updates need only its LOCAL rows. Collect the addressable
            # shards onto one local device and concatenate THERE — the
            # previous np.asarray round-trip moved O(local rows) through
            # the host on EVERY tree
            dev0 = jax.local_devices()[0]
            rows = jnp.concatenate(
                [jax.device_put(sh.data, dev0)
                 for sh in sorted(log.row_leaf.addressable_shards,
                                  key=lambda sh: sh.index[0].start or 0)])
            # leaf_value is consumed by the process-local score update: a
            # globally-replicated array cannot mix with the single-device
            # score (it is tiny — a host hop is fine)
            log = log._replace(
                row_leaf=rows[:n],
                leaf_value=jax.device_put(np.asarray(log.leaf_value), dev0))
        elif self.rows_sharded and self.padded_n != n:
            log = log._replace(row_leaf=log.row_leaf[:n])
        return log


class DataParallelTreeLearner(_MeshTreeLearner):
    """tree_learner=data: rows sharded, histograms globally reduced
    (reference: DataParallelTreeLearner)."""

    comm_mode = "data"
    rows_sharded = True


class FeatureParallelTreeLearner(_MeshTreeLearner):
    """tree_learner=feature: data replicated, split search sharded over
    features, winner synced — no data movement, comm is one SplitInfo per
    round (reference: FeatureParallelTreeLearner)."""

    comm_mode = "feature"
    rows_sharded = False


class VotingParallelTreeLearner(_MeshTreeLearner):
    """tree_learner=voting: data-parallel with top-k feature voting to
    bound comm volume as features grow (reference:
    VotingParallelTreeLearner / PV-Tree)."""

    comm_mode = "voting"
    rows_sharded = True


def create_tree_learner(config: Config, dataset: BinnedDataset,
                        mesh: Optional[Mesh] = None) -> SerialTreeLearner:
    """Factory (reference: src/treelearner/tree_learner.cpp:15
    CreateTreeLearner)."""
    kind = config.tree_learner
    if kind == "serial" or mesh is None or mesh.devices.size <= 1:
        return SerialTreeLearner(config, dataset)
    cls = {"data": DataParallelTreeLearner,
           "feature": FeatureParallelTreeLearner,
           "voting": VotingParallelTreeLearner}.get(kind)
    if cls is None:
        Log.fatal("Unknown tree_learner: %s", kind)
    return cls(config, dataset, mesh)
