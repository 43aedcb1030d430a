"""Training loop with FastPersist checkpointing as a first-class feature.

Implements the paper's Fig. 4 execution schedules, all driven through the
unified :class:`repro.core.engine.CheckpointEngine` — the trainer never
branches on the checkpointer implementation:

  baseline               : train step → rank-0 synchronous torch.save-style
                           write (completed SaveHandle)
  fastpersist            : train step → parallel NVMe write (completed
                           SaveHandle)
  fastpersist-pipelined  : write overlaps the next iteration's
                           forward/backward; we block before the next
                           optimizer step (here: before dispatching the
                           next train_step, which fuses F+B+O) until the
                           previous checkpoint committed (engine.wait()).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.checkpointer import FastPersistConfig
from repro.core.engine import CheckpointEngine, CheckpointSpec
from repro.core.retention import RetentionManager, RetentionPolicy
from repro.data.pipeline import DataConfig, TokenStream
from repro.models.registry import build_model
from repro.optim.adam import AdamConfig
from repro.train.steps import TrainState, init_train_state, make_train_step


@dataclass
class CheckpointPolicy:
    directory: str
    every: int = 1                     # paper: per-iteration
    mode: str = "fastpersist"          # fastpersist | baseline | none
    pipeline: bool = True
    backend: Optional[str] = None      # explicit engine backend name;
    #                                    overrides mode/pipeline when set
    fp: FastPersistConfig = field(default_factory=FastPersistConfig)
    retention: Optional[RetentionPolicy] = None   # None = keep everything
    #: shard destination volume roots (one per SSD/mount); None = all
    #: shards under ``directory`` (see CheckpointSpec.volumes)
    volumes: Optional[list] = None
    #: parallel-restore reader workers for ``Trainer.restore()``:
    #: "auto" sizes to the saved shard count (capped by CPUs), an int
    #: forces that many, None keeps the legacy single-reader load
    restore_readers: Optional[object] = "auto"
    #: second durability tier (DESIGN.md §8): object-store spec (path /
    #: ``file://`` / registered ``scheme://`` URL / ObjectStore). When
    #: set with mode="fastpersist", the engine runs a tiered backend:
    #: sealed generations stream to the store after each local commit,
    #: and ``Trainer.restore`` can hydrate from it (``tier="remote"``,
    #: or automatically when the local directory is empty/lost).
    upload: Optional[object] = None
    #: peer-replication tier (DESIGN.md §11): replication targets —
    #: ``[name=]store[@failure_domain]`` specs / PeerConfig / store
    #: objects. After each local commit the sealed generation streams
    #: to K peers in the background; ``Trainer.restore(tier="peer")``
    #: (or the automatic lost-node fallback) hydrates from the
    #: healthiest peer, falling back to the remote tier.
    replicate_peers: Optional[list] = None
    #: replicas each checkpoint should reach (spread across distinct
    #: failure domains when available)
    replication_factor: int = 2
    #: this node's failure domain — peer placement avoids it whenever
    #: any other usable domain exists
    failure_domain: Optional[str] = None
    #: incremental delta checkpoints (DESIGN.md §9): every Nth save is
    #: a full keyframe, the rest write only the dirty byte spans since
    #: the previous save. 1 (default) = every save is full. Requires
    #: the serialize arena; copied into ``fp.keyframe_every`` unless
    #: the FastPersistConfig already sets it.
    keyframe_every: int = 1
    #: range-fetch readers for remote/peer hydration (DESIGN.md §12):
    #: missing bytes are byte-striped across this many concurrent
    #: ranged GETs when the store supports them.
    hydrate_readers: int = 4
    #: serving read cache budget in MiB (DESIGN.md §12): 0 disables;
    #: > 0 routes hydration and per-tensor remote reads through a
    #: digest-keyed LRU block cache under ``<directory>/.serve-cache``.
    serve_cache_mb: int = 0

    def __post_init__(self):
        if self.keyframe_every > 1 and self.fp.keyframe_every == 1:
            import dataclasses
            self.fp = dataclasses.replace(self.fp,
                                          keyframe_every=self.keyframe_every)

    def backend_name(self) -> str:
        """Map the (legacy) mode/pipeline pair onto a registry key."""
        if self.backend is not None:
            return self.backend
        if self.mode == "fastpersist":
            if self.upload is not None:
                return ("fastpersist-tiered-pipelined" if self.pipeline
                        else "fastpersist-tiered")
            return "fastpersist-pipelined" if self.pipeline else "fastpersist"
        return self.mode                # "baseline" or any registered key


@dataclass
class StepRecord:
    """What one train step left behind."""
    step: int
    loss: object                       # device scalar; float() syncs
    #: seconds the step waited on checkpoints: the previous save's
    #: snapshot, and this step's ``engine.save`` (its throttle included)
    ckpt_stall: float
    save: Optional[object] = None      # SaveHandle of this step's save


@dataclass
class TrainerConfig:
    model: ModelConfig
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    gas: int = 1
    seed: int = 0
    opt: AdamConfig = field(default_factory=AdamConfig)
    checkpoint: Optional[CheckpointPolicy] = None
    log_every: int = 10


class Trainer:
    def __init__(self, cfg: TrainerConfig):
        self.cfg = cfg
        self.model = build_model(cfg.model)
        self.data = TokenStream(DataConfig(cfg.model.vocab_size,
                                           cfg.seq_len, cfg.global_batch,
                                           cfg.seed))
        self.train_step = jax.jit(
            make_train_step(self.model, cfg.opt, cfg.gas), donate_argnums=0)
        self.state: Optional[TrainState] = None
        self.engine: Optional[CheckpointEngine] = None
        self._retain = None
        self.ckpt_stall = 0.0
        #: one :class:`StepRecord` per step run
        self.history: list = []
        if cfg.checkpoint and cfg.checkpoint.backend_name() != "none":
            self._setup_checkpointer(cfg.checkpoint)
        # back-compat alias: older code/tests reach the checkpointer via
        # trainer._ckpt; the engine serves the same latest_step/load API
        self._ckpt = self.engine

    def _setup_checkpointer(self, pol: CheckpointPolicy):
        self.engine = CheckpointEngine(CheckpointSpec(
            directory=pol.directory, backend=pol.backend_name(), fp=pol.fp,
            volumes=pol.volumes, upload_store=pol.upload,
            peers=pol.replicate_peers,
            replication_factor=pol.replication_factor,
            failure_domain=pol.failure_domain,
            hydrate_readers=pol.hydrate_readers,
            serve_cache_mb=pol.serve_cache_mb))
        # GC must follow the same volume mapping the engine writes with,
        # or deleting a step would strand its striped shards; with an
        # upload or peer tier it must also see those queues, so it never
        # deletes a step whose remote COMMIT has not landed (DESIGN §8)
        # or whose replication is still short of the target (DESIGN §11)
        self._retain = (RetentionManager(pol.directory, pol.retention,
                                         self.engine.volume_roots(),
                                         upload=self.engine.upload_manager,
                                         peers=self.engine.peer_replicator)
                        if pol.retention else None)

    # ------------------------------------------------------------ state
    def init_state(self, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(self.cfg.seed)
        self.state = init_train_state(self.model, rng)
        if self.engine is not None:
            # fresh state object: same explicit arena-invalidation rule
            # as restore() (buffers the arena's cached layout was built
            # against may have been donated back to XLA already)
            self.engine.invalidate_arena()
        return self.state

    def restore(self, step: Optional[int] = None,
                tier: str = "local") -> int:
        """Resume from the most recent committed checkpoint (any
        backend — the COMMIT marker records which one wrote it), through
        the PARALLEL restore pipeline (paper §4.2: N reader workers,
        owned spans, async read backends — ``restore_readers`` in the
        policy). Returns the step.

        ``tier="peer"`` forces hydration from the peer-replication tier
        (DESIGN.md §11; itself falling back to remote when no peer
        holds a complete chain); ``tier="remote"`` from the object
        tier. With the default ``"local"``, a trainer whose local
        directory holds no committed step walks the tiers
        automatically — peer first when the policy replicates to
        peers, then the upload store (the lost-node recovery path —
        DESIGN.md §8/§11).

        Spans: ``restore`` round the call (tagged with the ``step``
        asked for, -1 for the newest), with the children
        ``restore.find`` (the newest committed step), ``restore.load``
        (``engine.load``: read, CRC check, deserialize) and
        ``restore.h2d`` (the copy onto the device, until it landed); the
        bytes copied add to the counter ``restore.bytes``."""
        assert self.engine is not None, "no checkpoint engine configured"
        if tier not in ("local", "peer", "remote"):
            raise ValueError(f"tier must be 'local', 'peer' or "
                             f"'remote', got {tier!r}")
        with obs.span("restore", step=-1 if step is None else step):
            return self._restore(step, tier)

    def _restore(self, step: Optional[int], tier: str) -> int:
        forced = tier != "local"
        use_tier = tier
        latest = None
        if step is None and not forced:
            with obs.span("restore.find"):
                latest = self.engine.latest_step()
        if step is None and latest is None and not forced:
            if self.engine.peer_replicator is not None:
                use_tier = "peer"       # local tier empty/lost → peer
            elif self.engine.remote_store is not None:
                use_tier = "remote"     # ... → remote
        if use_tier == "local":
            step = step if step is not None else latest
            if step is None:
                return 0
        # the pytree structure comes from the abstract state: resume
        # never materialises a throwaway initial state on the device
        like = jax.eval_shape(lambda rng: init_train_state(self.model, rng),
                              jax.random.PRNGKey(self.cfg.seed))
        readers = (self.cfg.checkpoint.restore_readers
                   if self.cfg.checkpoint else None)
        try:
            with obs.span("restore.load"):
                restored, manifest = self.engine.load(
                    step, like=like, parallel=readers, tier=use_tier)
        except FileNotFoundError:
            # only the AUTOMATIC fallback may degrade to a fresh start;
            # an operator who explicitly asked for the peer/remote tier
            # must hear that it is empty (a mistyped store path would
            # otherwise silently retrain from scratch and shadow the
            # real history)
            if use_tier != "local" and step is None and not forced:
                return 0                # no tier has a checkpoint
            raise
        # release the old state (and the arena's device-resident
        # images) BEFORE the host->device copy, so the device holds one
        # copy of the state at any time. Donation hook too: the state
        # object is replaced wholesale — invalidate the serialize
        # arena's cached layout explicitly rather than trusting the
        # structure key to notice
        self.state = None
        self.engine.invalidate_arena()
        # jnp.array COPIES: a parallel load returns views into the
        # engine's read arena, which the next load would refill —
        # the trainer's state must own its buffers (DESIGN.md §7).
        # The restore returns once the state is on the device.
        with obs.span("restore.h2d"):
            self.state = jax.block_until_ready(
                jax.tree.map(jax.numpy.array, restored))
        obs.count("restore.bytes", sum(x.nbytes for x in
                                       jax.tree.leaves(self.state)))
        extras = manifest.extras
        if "data" in extras:
            self.data = TokenStream.from_state(self.data.cfg, extras["data"])
        return int(extras.get("step", step if step is not None else 0))

    # ------------------------------------------------------------- loop
    def _save(self, step: int):
        extras = {"step": step, "data": self.data.state()}
        return self.engine.save(self.state, step, extras)

    def run(self, start_step: int = 0):
        """Steps ``start_step`` .. ``cfg.steps``. Spans per step:
        ``train.data`` (the next batch), ``train.dispatch`` (enqueueing
        the step) and, on a save step, ``train.save`` (waiting for the
        new params, then ``engine.save``)."""
        if self.state is None:
            self.init_state()
        pol = self.cfg.checkpoint
        metrics = {}
        t_log, logged = time.perf_counter(), start_step
        for step in range(start_step, self.cfg.steps):
            stall = 0.0
            handle = None
            with obs.span("train.data"):
                batch = next(self.data)
            if self.engine is not None and self.engine.async_save:
                # §4.3 sync point (DESIGN.md §10): train_step donates the
                # state's buffers, so the previous checkpoint's snapshot
                # must no longer read them — once every leaf has crossed
                # to the host. The rest of the snapshot (the copy into
                # the arena) and the NVMe writes keep overlapping this
                # iteration; the engine's submit throttle + drain() stay
                # the durability sync points.
                stall += self.engine.wait_snapshot()
            with obs.span("train.dispatch"):
                self.state, metrics = self.train_step(self.state, batch)
            if pol and self.engine is not None \
                    and (step + 1) % pol.every == 0:
                with obs.span("train.save", step=step + 1) as save:
                    jax.block_until_ready(self.state.params)
                    computing = time.perf_counter() - save.t0
                    handle = self._save(step + 1)
                # the wait for the step's own compute is not a stall
                stall += save.seconds - computing
                if self._retain is not None:
                    self._retain.after_commit()
            self.ckpt_stall += stall
            self.history.append(StepRecord(step + 1, metrics["loss"],
                                           stall, handle))
            if (step + 1) % self.cfg.log_every == 0:
                loss = float(metrics["loss"])      # waits for the step
                now = time.perf_counter()
                it = (now - t_log) / (step + 1 - logged)
                t_log, logged = now, step + 1
                print(f"step {step+1}: loss={loss:.4f} it={it*1e3:.1f}ms")
        if self.engine is not None:
            with obs.span("train.drain") as drain:
                self.engine.drain()  # commit stragglers, park the worker
                # a CLEAN exit also flushes the upload AND peer tiers
                # (the workers are daemon threads — returning now would
                # abandon the tail generations' remote/peer COMMITs; a
                # crash still degrades to the last fully-uploaded /
                # fully-replicated generation, DESIGN §8/§11)
                self.engine.wait_uploaded()
                self.engine.wait_replicated()
            self.ckpt_stall += drain.seconds
        jax.block_until_ready(self.state.params)
        return self.state, metrics
