"""Elastic training: survive data-parallel host loss without a job restart.

PR 1 made host loss survivable at *checkpoint* granularity — lose a worker,
relaunch the fleet, reload from disk, rewind the dataloader. On a preemptible
pod that is minutes of lost work per eviction. This module closes the gap
in-memory: because the ZeRO sharded update (parallel/zero.py, arXiv
2004.13336) already stores the authoritative params/grads/optimizer state
1/N over the data axes, losing a host destroys only the shards that lived on
it — and a *buddy-redundant* copy of each shard (mirrored to a rank on a
different host, Oobleck/Bamboo-style) means no shard has a single point of
failure. Recovery is then a relayout, not a restart: pause at a step
boundary, reassemble the state from surviving shards, reshard onto the
shrunken N−k mesh (the same save→load reshard path PR 11 pinned bit-exact),
re-partition the global batch over the survivors, recompile the step, and
resume. Losing a host costs seconds, not a job.

The degradation ladder (every rung chaos-drilled, mirroring the serving
fleet's handoff ladder in serving/router.py):

1. **buddy reshard** — redundancy on and the mirror fresh (refreshed at the
   last step boundary): every lost shard is read from its buddy copy on a
   surviving host; zero steps lost, recovery is mirror-read + reshard +
   recompile.
2. **checkpoint reload** — no redundancy, the buddy also died, or the mirror
   is stale (``mirror_every > 1`` and the loss landed between refreshes — a
   stale buddy mixed with fresh survivor shards would be a state from two
   different steps, which is worse than losing steps): reload the newest
   valid checkpoint onto the survivor mesh and rewind the dataloader
   (fault_tolerance.py's machinery); steps since that checkpoint are lost.
3. **fail loudly** — no checkpoint either: raise :class:`ElasticFailure`
   naming what was tried. Silent corruption is never on the ladder.

Regrow rides the same path in reverse: when the lost host revives, the live
state (all shards readable — nothing lost) reshards onto the full mesh and
the step recompiles once.

Simulation model (what the CPU tests drill): the 8-device virtual mesh is
partitioned into ``num_hosts`` contiguous host groups; "losing host i" makes
every buffer on its devices unreadable from that instant — recovery code
NEVER reads a shard on a lost device (enforced in
:func:`assemble_from_survivors`, not assumed). On a real pod the same
coordinator runs per-process; *naming* the lost host is the ``membership=``
probe's job (:mod:`~.membership`: epoch-fenced heartbeats, the
silence/step-stall failure detector, supervisor-published deaths, and
join-record re-admission), with the chaos hook standing in for drills. The
``jax.distributed`` re-rendezvous across surviving processes sits behind
``PartialState.rejoin()`` (env-gated on real hardware); validating it on a
pod is the ROADMAP's multi-slice-elasticity remainder. The host-relay
reassembly (read surviving shards → host → device_put onto the new mesh,
one leaf at a time to bound peak host memory) is the CPU stand-in for the
2112.01075 device-to-device redistribution collective, exactly like the
serving fleet's KV handoff.

Everything is observable: every detection/recovery/regrow lands as a
``{"kind": "elastic"}`` record in telemetry.jsonl with an ``mttr_s`` field,
recovery wall time feeds the goodput ledger as ``elastic_reshard``, and the
resharded step program is contract-gated like any other (the PR 8
differential gate and the replication audit run against the shrunken mesh).
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

import jax

from ..logging import get_logger

logger = get_logger(__name__)


class ElasticFailure(RuntimeError):
    """Every rung of the elastic degradation ladder failed (or the survivor
    set cannot form a mesh). The run cannot continue correctly — failing
    loudly here is the ladder's last rung, by design."""


@dataclass
class ElasticConfig:
    """Opt-in elastic-training knobs (``Accelerator.elastic_coordinator``).

    - ``redundancy`` — buddy copies of each rank's ZeRO shard (0 = none: the
      ladder starts at the checkpoint rung). Each copy costs an extra
      (params + optimizer state)/N of HBM per chip — priced by
      ``estimate-memory --elastic-redundancy`` and recorded in telemetry
      when the mirror is allocated. Only 0 and 1 are meaningful on a
      single-roll mirror; values >1 are rejected.
    - ``num_hosts`` — how many (simulated) hosts the device mesh divides
      into; "host loss" removes one contiguous group of
      ``num_devices/num_hosts`` devices. Defaults to ``jax.process_count()``
      (the real-pod mapping: one process per host).
    - ``mirror_every`` — refresh the buddy mirror every N completed steps.
      1 (default) keeps the mirror always fresh so the buddy rung loses zero
      steps; larger values cut mirror bandwidth but any loss landing between
      refreshes falls through to the checkpoint rung (a stale mirror cannot
      be mixed with fresh survivor shards — see the ladder).
    - ``checkpoint_dir`` — where the checkpoint rung looks for the newest
      valid checkpoint (``fault_tolerance.latest_valid_checkpoint``).
    - ``contracts_dir`` — when set, the resharded step program is checked
      against the checked-in contracts after every reshard (PR 8 gate; on a
      shrunken mesh the env-pinned contract degrades to an explicit skip,
      never fabricated drift — and the replication audit must stay clean).
    - ``handle_signals`` — install a SIGUSR1 handler that flags a shrink
      request for the next step boundary: the transport half of
      ``pod-launch --elastic``, whose supervisor signals the SURVIVORS of a
      partial failure instead of relaunching the fleet.
    """

    redundancy: int = 1
    num_hosts: Optional[int] = None
    mirror_every: int = 1
    checkpoint_dir: Optional[str] = None
    contracts_dir: Optional[str] = None
    handle_signals: bool = False

    def __post_init__(self):
        if self.redundancy not in (0, 1):
            raise ValueError(
                f"ElasticConfig.redundancy must be 0 or 1 (one buddy roll), got {self.redundancy}"
            )
        if self.mirror_every < 1:
            raise ValueError("ElasticConfig.mirror_every must be >= 1")


# ---------------------------------------------------------------------------
# host groups / buddy layout
# ---------------------------------------------------------------------------


def host_device_groups(devices: list, num_hosts: int) -> list[list]:
    """Partition ``devices`` (mesh flat order) into ``num_hosts`` contiguous
    groups — the simulation's host boundaries. Contiguity matters: the buddy
    roll distance is one host's worth of ranks, so a shard and its buddy can
    never share a host."""
    n = len(devices)
    if num_hosts < 1 or n % num_hosts != 0:
        raise ValueError(
            f"{n} devices do not divide into {num_hosts} equal hosts"
        )
    per = n // num_hosts
    return [list(devices[i * per : (i + 1) * per]) for i in range(num_hosts)]


def buddy_mesh(mesh: jax.sharding.Mesh, stride: int) -> jax.sharding.Mesh:
    """The buddy placement mesh: the same axes over the device list rolled by
    ``stride`` (= devices per host), so rank r's shard lands on rank
    r+stride's device — a different host by construction. A buddy array is
    simply the primary array ``device_put`` onto this mesh with the SAME
    PartitionSpec: identical global value, shard-for-shard displaced one
    host over."""
    flat = mesh.devices.reshape(-1)
    if not 0 < stride < flat.size:
        raise ValueError(f"buddy stride {stride} out of range for {flat.size} devices")
    rolled = np.roll(flat, stride).reshape(mesh.devices.shape)
    return jax.sharding.Mesh(rolled, mesh.axis_names)


def buddy_shardings(shardings: Any, bmesh: jax.sharding.Mesh) -> Any:
    """Primary NamedShardings → the buddy layout (same specs, rolled mesh)."""
    from jax.sharding import NamedSharding

    return jax.tree.map(lambda s: NamedSharding(bmesh, s.spec), shardings)


# ---------------------------------------------------------------------------
# survivor-side reassembly (the honest read path: lost devices are unreadable)
#
# These primitives moved to parallel/redistribute.py — the coverage pre-check
# and the per-leaf host relay ARE the fallback rung of the one redistribution
# primitive every recovery path now routes through — and are re-exported here
# so the ladder's callers (and the drills) keep their import path.
# ---------------------------------------------------------------------------

from ..parallel.redistribute import (  # noqa: E402,F401 - re-exported API
    _index_key,
    _leaf_covered,
    assemble_from_survivors,
    relay_tree,
    tree_covered,
)


# ---------------------------------------------------------------------------
# the coordinator
# ---------------------------------------------------------------------------


class ElasticCoordinator:
    """Owns one training run's elastic lifecycle: the compiled step, the
    buddy mirror, host-loss detection (chaos plan or supervisor signal), the
    recovery ladder, and regrow.

    Canonical loop (the compiled-step loop, elastics riding along)::

        coordinator = accelerator.elastic_coordinator(
            loss_fn, config=ElasticConfig(redundancy=1, num_hosts=2),
            checkpoint_manager=manager,
        )
        for batch in loader:
            loss = coordinator.step(batch)   # host-loss pauses, reshards, resumes here
        # lost host came back:
        coordinator.regrow()

    ``step`` accepts host (numpy) batches or already-sharded global arrays;
    host batches are sharded with the LIVE ``data_sharding`` so the global
    batch re-partitions over the survivors automatically after a shrink —
    same rows, fewer ranks, no example skipped or repeated (prepared
    dataloaders do the same through their own live ``_globalize``).
    """

    def __init__(
        self,
        accelerator: Any,
        loss_fn: Callable,
        model: Any = None,
        optimizer: Any = None,
        config: Optional[ElasticConfig] = None,
        checkpoint_manager: Any = None,
        membership: Any = None,
        **step_kwargs: Any,
    ):
        self.accelerator = accelerator
        self.config = config or ElasticConfig()
        if model is None:
            if not accelerator._models:
                raise ValueError("ElasticCoordinator needs a prepared model.")
            model = accelerator._models[-1]
        self.model = model
        if optimizer is None:
            optimizer = next(
                (o for o in accelerator._optimizers if o._box is self.model.box), None
            )
            if optimizer is None:
                raise ValueError(
                    "ElasticCoordinator needs an optimizer prepared for this "
                    "model — call prepare_optimizer() first."
                )
        self.optimizer = optimizer
        if self.optimizer.cpu_offload:
            raise ValueError(
                "ElasticCoordinator does not compose with cpu_offload "
                "optimizer state (the buddy mirror and survivor reassembly "
                "cover device shards); keep the state on-device or drop "
                "elastic training."
            )
        self._loss_fn = loss_fn
        self._step_kwargs = step_kwargs
        self.checkpoint_manager = checkpoint_manager
        if self.checkpoint_manager is not None and self.config.checkpoint_dir is None:
            self.config = dataclasses.replace(
                self.config, checkpoint_dir=self.checkpoint_manager.checkpoint_dir
            )
        num_hosts = self.config.num_hosts or max(int(jax.process_count()), 1)
        # host groups are fixed over the ORIGINAL full mesh: regrow restores
        # exactly these devices, and a second loss indexes the same groups
        self._full_devices = list(self.accelerator.mesh.devices.reshape(-1))
        # pinned with EXPLICIT axis sizes so a full regrow restores the
        # original layout bit-for-bit (not a different equal-sized factoring)
        shape = self.accelerator.mesh.shape
        self._full_parallelism = dataclasses.replace(
            accelerator.state.parallelism,
            data=int(shape.get("data", 1)),
            fsdp=int(shape.get("fsdp", 1)),
        )
        self.host_groups = host_device_groups(self._full_devices, num_hosts)
        self.lost_hosts: set[int] = set()
        self.completed_steps = 0
        self._mirror_step = -1
        self._buddy: Optional[dict] = None
        self._shrink_requested = False
        self._batch_struct = None
        self.last_recovery: Optional[dict] = None
        self.recoveries: list[dict] = []
        # the membership probe (resilience/membership.py): epoch-fenced
        # heartbeats + failure detector, so a supervisor signal — or plain
        # heartbeat silence — resolves to a NAMED lost host instead of the
        # PR 12 warning. Explicit object, or ACCELERATE_MEMBERSHIP_DIR (the
        # pod-launch --elastic --membership_dir transport).
        if membership is None:
            from .membership import MembershipService

            # one membership identity per host: an out-of-range process
            # index raises in the service (aliasing identities would mask
            # a real death), so the mismatch surfaces at construction
            membership = MembershipService.from_env(
                num_hosts=len(self.host_groups),
                host_index=int(jax.process_index()),
            )
        self.membership = membership
        self._hang_watchdog = None
        self._last_membership_io: Optional[float] = None
        # single-controller simulation publishes one beat per SIMULATED
        # host; a real multi-process pod must publish ONLY its own — peers
        # refreshing a dead host's record would blind the silence detector
        self._sim_publish = int(jax.process_count()) <= 1
        if self.membership is not None:
            if self.membership.num_hosts != len(self.host_groups):
                raise ValueError(
                    f"membership service tracks {self.membership.num_hosts} hosts "
                    f"but the coordinator simulates {len(self.host_groups)} — the "
                    "two views would name different hosts for the same rank"
                )
            if self.membership.telemetry is None:
                self.membership.telemetry = getattr(self.accelerator, "telemetry", None)
            hang_timeout = self.membership.config.hang_watchdog_timeout_s
            if hang_timeout is not None:
                from .membership import CollectiveHangWatchdog

                self._hang_watchdog = CollectiveHangWatchdog(self.membership, hang_timeout)
        self.signals_armed = False
        self._recompile()
        if self.config.redundancy:
            self._mirror()
        if self.config.handle_signals:
            self._install_signal_handler()

    def _install_signal_handler(self) -> None:
        """SIGUSR1 → shrink request at the next boundary (the signal the
        elastic pod supervisor sends survivors). Flag-only, exactly like
        CheckpointManager's preemption handler — never reshard from a
        handler: the interrupted step's state is inconsistent."""
        import signal

        try:
            signal.signal(signal.SIGUSR1, lambda signum, frame: self.request_shrink())
            self.signals_armed = True
        except ValueError:
            # signal.signal only works on the main thread: a library-embedded
            # coordinator (server thread, notebook executor) must still
            # construct — degrade to a warning and an unarmed handler
            # (``signals_armed`` stays False so callers can check)
            logger.warning(
                "ElasticCoordinator could not install the SIGUSR1 handler "
                "outside the main thread — handler left UNARMED; call "
                "request_shrink() from your own signal plumbing, or rely on "
                "the membership= probe (which needs no signal at all)."
            )

    # -- surfaces ------------------------------------------------------------

    @property
    def mesh(self):
        return self.accelerator.mesh

    @property
    def num_hosts(self) -> int:
        return len(self.host_groups)

    def surviving_devices(self) -> list:
        lost = self._lost_device_ids(self.lost_hosts)
        return [d for d in self._full_devices if d.id not in lost]

    def shard_batch(self, batch: Any) -> Any:
        """Place a host batch onto the LIVE mesh's data sharding (re-derived
        every call, so post-shrink batches repartition over the survivors).

        A DEVICE batch still laid out for a pre-shrink mesh is salvaged
        through surviving shards only — the module's no-dead-reads invariant
        holds for batches too (a plain ``np.asarray`` would gather the lost
        host's buffers: silent in the simulation, a hang on real hardware).
        Rows that lived only on lost devices are genuinely gone and raise
        :class:`ElasticFailure` naming the two working patterns (feed host
        batches, or let a prepared dataloader's next yield re-shard itself
        from its retained host copy)."""
        sharding = self.accelerator.state.data_sharding()
        lost_ids = self._lost_device_ids(self.lost_hosts)

        def _put(x):
            if isinstance(x, jax.Array):
                if x.sharding.mesh == self.mesh:
                    return x
                host = assemble_from_survivors(x, lost_ids)
                if host is None:
                    raise ElasticFailure(
                        "a device batch laid out for the pre-shrink mesh has "
                        "rows only on LOST devices — they cannot be read. "
                        "Feed coordinator.step() host (numpy) batches, or "
                        "iterate a prepared dataloader (its next batch "
                        "re-shards itself onto the survivor mesh); the "
                        "checkpoint rung replays positions via "
                        "CheckpointManager.resumed_loader."
                    )
                return jax.device_put(host, sharding)
            return jax.device_put(np.asarray(x), sharding)

        return jax.tree.map(_put, batch)

    def request_shrink(self) -> None:
        """Out-of-band loss notification (the pod supervisor's SIGUSR1 /
        peer-death signal): the next ``step`` boundary probes the chaos plan
        for the lost host and reshards before stepping."""
        self._shrink_requested = True

    # -- the step ------------------------------------------------------------

    def step(self, batch: Any):
        """One training step with the elastic boundary check in front: a
        host loss scheduled for this step (chaos), signalled by the
        supervisor, or named by the membership detector pauses the run,
        walks the recovery ladder, and resumes on the shrunken mesh — the
        step then executes there. A pending membership join record turns
        into ``regrow()`` at the same boundary (re-admission without a
        barrier stall)."""
        membership_due = False
        if self.membership is not None:
            # an explicit shrink request forces a FULL membership boundary
            # (publish, then detect) regardless of the throttle — detection
            # must read beats from THIS boundary, not interval-stale ones
            membership_due = self._membership_due() or self._shrink_requested
            if membership_due:
                self._membership_boundary()
        lost = self._detect_loss(membership_due)
        if lost is not None:
            self.reshard(lost)
        from ..parallel.sharding import abstract_like

        batch = self.shard_batch(batch)
        self._batch_struct = abstract_like(batch)
        if self._hang_watchdog is not None:
            self._hang_watchdog.arm()
        try:
            loss = self._step(batch)
        finally:
            if self._hang_watchdog is not None:
                self._hang_watchdog.disarm()
        self.completed_steps += 1
        if self.config.redundancy and self.completed_steps % self.config.mirror_every == 0:
            self._mirror()
        return loss

    def _membership_due(self) -> bool:
        """Whether this boundary does membership store work. Throttled by
        ``MembershipConfig.min_probe_interval_s`` so sub-second steps on a
        network-filesystem store don't pay fsync'd I/O per step; 0 (the
        default, and every drill) probes every boundary."""
        interval = self.membership.config.min_probe_interval_s
        if interval <= 0:
            return True
        now = time.monotonic()
        if self._last_membership_io is None or now - self._last_membership_io >= interval:
            self._last_membership_io = now
            return True
        return False

    def _membership_degraded(self, op: str, error: Exception) -> None:
        """Store weather outlasted STORE_RETRY: degrade THIS boundary's
        membership work to a warning instead of killing the training run
        the service exists to protect — losing one boundary of detection is
        strictly better than losing the job. The next boundary retries."""
        logger.warning(
            f"elastic: membership {op} degraded (store unreachable past its "
            f"retry budget: {error}); detection skipped this boundary."
        )
        try:
            self.membership._record(
                "store_degraded", {"op": op, "error": str(error)}
            )  # telemetry is local — no store I/O on this path
        except Exception:  # noqa: BLE001 - degradation reporting must not raise
            pass

    def _membership_boundary(self) -> None:
        """The membership half of the step boundary: admit pending joins
        (turning the revived host's join record into ``regrow()``), then
        publish this boundary's heartbeats. Under the single controller the
        coordinator publishes one beat per SIMULATED host (chaos legs
        silence or freeze individual hosts); on a real pod each process
        publishes only its own through the identical surface. Store I/O
        failures degrade (see :meth:`_membership_degraded`); a failure
        inside ``regrow`` itself stays loud — that is recovery, not
        bookkeeping."""
        try:
            pending = self.membership.pending_joins()
        except Exception as e:  # noqa: BLE001 - store weather must not kill the run
            self._membership_degraded("pending_joins", e)
            pending = []
        joins = [h for h in pending if h in self.lost_hosts]
        if joins:
            self.regrow(hosts=joins)
        for host in pending:
            if host in joins or host in self.lost_hosts:
                continue
            # a join record this coordinator cannot regrow (the host was
            # never lost from ITS mesh — e.g. the coordinator restarted, or
            # the record is moot because the host is already a member):
            # resolve it at the membership level so it doesn't re-list
            # forever and the joiner doesn't wait on nobody
            try:
                if host in self.membership.view()["members"]:
                    self.membership.store.delete(f"join/{host}")
                else:
                    self.membership.admit(host)
            except Exception as e:  # noqa: BLE001
                self._membership_degraded("admit_stale_join", e)
        plan = getattr(getattr(self.accelerator, "resilience", None), "chaos", None)
        boundary = self.completed_steps + 1  # 1-based, like host_loss
        publish_for = (
            range(self.num_hosts) if self._sim_publish else (self.membership.host_index,)
        )
        for host in publish_for:
            if host in self.lost_hosts:
                continue
            step = self.completed_steps
            if plan is not None:
                if plan.membership_silent(host, boundary):
                    continue
                frozen = plan.membership_stall(host, boundary)
                if frozen is not None:
                    step = frozen
            try:
                self.membership.heartbeat(step, host=host)
            except Exception as e:  # noqa: BLE001 - store weather must not kill the run
                self._membership_degraded("heartbeat", e)
                break

    def _membership_probe(self) -> Optional[int]:
        """Ask the failure detector for a named lost host this boundary can
        act on. Suspicions the survivor mesh cannot absorb are skipped (the
        detector keeps returning them, so a later boundary — e.g. after a
        regrow — can still act). Store failures degrade to 'no detection
        this boundary', never to a crashed run."""
        try:
            suspicions = self.membership.detect()
        except Exception as e:  # noqa: BLE001 - store weather must not kill the run
            self._membership_degraded("detect", e)
            return None
        for suspicion in suspicions:
            if self._loss_valid(suspicion["host"]):
                logger.warning(
                    f"elastic: membership detector named host "
                    f"{suspicion['host']} lost ({suspicion['reason']}, "
                    f"mttd {suspicion['mttd_s']:.3f}s)"
                )
                return suspicion["host"]
        return None

    def _detect_loss(self, membership_due: bool = False) -> Optional[int]:
        plan = getattr(getattr(self.accelerator, "resilience", None), "chaos", None)
        requested, self._shrink_requested = self._shrink_requested, False
        lost = None
        if plan is not None:
            boundary = self.completed_steps + 1  # 1-based, like the training chaos legs
            lost = plan.host_loss(boundary, valid=self._loss_valid)
            if lost is None and requested:
                # supervisor-signalled: the plan carries which host (the
                # probe); fire it regardless of the scheduled step
                lost = plan.host_loss(plan.host_loss_step, valid=self._loss_valid)
        if lost is None and self.membership is not None and (membership_due or requested):
            # a supervisor request always probes (step() ran the boundary
            # publish for it too) — the throttle paces only the background
            # cadence, never an explicit signal
            lost = self._membership_probe()
        if lost is None and requested:
            # a shrink was requested but nothing can name the lost host —
            # swallowing the signal silently would leave the run stepping
            # toward a hung collective with no explanation. The membership
            # probe is the production answer (the supervisor publishes the
            # dead index into its store, and the detector names silent or
            # wedged hosts on its own); the chaos plan remains the drill
            # probe. Say so where the operator will look.
            logger.warning(
                "elastic: shrink requested (supervisor signal) but no host "
                "probe identified the lost host. The run continues on the "
                "FULL mesh; if a host is really gone, the next collective "
                "will hang. Wire a membership= probe (pod-launch --elastic "
                "--membership_dir, or elastic_coordinator(..., "
                "membership=MembershipService(...))), arm "
                "ACCELERATE_CHAOS_HOST_LOSS_STEP/_INDEX (drills), or call "
                "coordinator.reshard(lost_host=...) directly."
            )
            telemetry = getattr(self.accelerator, "telemetry", None)
            if telemetry is not None and telemetry.enabled:
                telemetry.write_record(
                    "elastic",
                    {"event": "shrink_request_unresolved", "at_step": self.completed_steps},
                )
        return lost

    def _loss_valid(self, host_index: int) -> bool:
        if not 0 <= host_index < self.num_hosts or host_index in self.lost_hosts:
            return False
        # the survivors must still form a mesh — the strict model axes must
        # divide and a batch axis must absorb the shrink — or the injection
        # would drill nothing
        remaining = len(self.surviving_devices()) - len(self.host_groups[host_index])
        return remaining > 0 and self._shrunk_parallelism(remaining) is not None

    # -- buddy mirror --------------------------------------------------------

    def _devices_per_host(self) -> int:
        return len(self._full_devices) // self.num_hosts

    def _mirror(self) -> None:
        """Refresh the buddy copy of the step-boundary state: params +
        optimizer state (the authoritative 1/N shards) device_put onto the
        rolled mesh, one leaf at a time: each leaf's put is a
        collective-permute over every device, and with many in flight at once
        XLA:CPU under load can wait on a participant that never starts and
        abort the process. Gradients are recomputed, the scaler scalars are
        replicated everywhere already — neither needs a buddy. With only one
        host's devices left there is nowhere redundant to roll onto: the
        mirror stands down (a further loss falls to the checkpoint rung)."""
        per_host = self._devices_per_host()
        if self.mesh.devices.size <= per_host:
            if self._buddy is not None:
                logger.warning(
                    "elastic: one host's devices remain — buddy mirror stood "
                    "down; a further loss degrades to the checkpoint rung."
                )
            self._buddy = None
            return
        bmesh = buddy_mesh(self.mesh, per_host)
        p_sh = buddy_shardings(self.model.params_shardings, bmesh)
        o_sh = buddy_shardings(self.optimizer._opt_state_device_shardings, bmesh)
        first_mirror = self._buddy is None

        def put(tree, shardings):
            return jax.tree.map(lambda x, s: jax.block_until_ready(jax.device_put(x, s)), tree, shardings)

        self._buddy = {
            "params": put(self.model.params, p_sh),
            "opt_state": put(self.optimizer.opt_state, o_sh),
        }
        self._mirror_step = self.completed_steps
        if first_mirror:
            self._record_mirror_cost()

    def _record_mirror_cost(self) -> None:
        telemetry = getattr(self.accelerator, "telemetry", None)
        if telemetry is None or not telemetry.enabled:
            return
        from ..telemetry.memory import state_bytes_per_chip

        telemetry.write_record(
            "elastic",
            {
                "event": "redundancy_allocated",
                "redundancy": self.config.redundancy,
                "buddy_bytes_per_chip": state_bytes_per_chip(self._buddy["params"])
                + state_bytes_per_chip(self._buddy["opt_state"]),
                "mirror_every": self.config.mirror_every,
            },
        )

    def _buddy_fresh(self) -> bool:
        return self._buddy is not None and self._mirror_step == self.completed_steps

    # -- recovery ladder -----------------------------------------------------

    def _lost_device_ids(self, hosts) -> set:
        return {d.id for h in hosts for d in self.host_groups[h]}

    def _shrunk_parallelism(self, n_devices: int):
        """The ParallelismConfig for ``n_devices`` survivors, or None when no
        layout fits. The strict model axes (pipeline/expert/sequence/tensor)
        are fixed — their collectives are baked into the program structure.
        The BATCH axes absorb the shrink: data first (keeping fsdp), else
        fsdp (keeping data — fsdp is a weight-update shard axis, resizable
        like data). The full device set restores the original layout exactly
        (regrow must not land on a different-but-equal-sized mesh)."""
        if n_devices == len(self._full_devices):
            return self._full_parallelism
        par = self.accelerator.state.parallelism
        shape = self.accelerator.mesh.shape
        strict = int(
            shape.get("pipeline", 1) * shape.get("expert", 1)
            * shape.get("sequence", 1) * shape.get("tensor", 1)
        )
        data, fsdp = int(shape.get("data", 1)), int(shape.get("fsdp", 1))
        if n_devices >= strict * fsdp and n_devices % (strict * fsdp) == 0:
            return dataclasses.replace(par, data=n_devices // (strict * fsdp), fsdp=fsdp)
        if n_devices >= strict * data and n_devices % (strict * data) == 0:
            return dataclasses.replace(par, data=data, fsdp=n_devices // (strict * data))
        return None

    def reshard(self, lost_host: int) -> dict:
        """Walk the degradation ladder for the loss of ``lost_host``; on
        success the accelerator/model/optimizer live on the shrunken mesh
        with a freshly compiled step. Raises :class:`ElasticFailure` from
        the last rung."""
        t0 = time.perf_counter()
        telemetry = getattr(self.accelerator, "telemetry", None)
        telemetry = telemetry if (telemetry is not None and telemetry.enabled) else None
        self.lost_hosts.add(lost_host)
        lost_ids = self._lost_device_ids(self.lost_hosts)
        survivors = self.surviving_devices()
        if telemetry is not None:
            telemetry.write_record(
                "elastic",
                {
                    "event": "host_loss_detected",
                    "host": lost_host,
                    "lost_devices": sorted(lost_ids),
                    "survivors": len(survivors),
                    "at_step": self.completed_steps,
                },
            )
        if not survivors or self._shrunk_parallelism(len(survivors)) is None:
            # routed through _fail so a mesh-infeasible loss still records
            # recovery_failed (a direct mid-ladder raise would bypass it)
            raise self._fail(
                lost_host, t0, telemetry,
                tried=[],
                reason=f"{len(survivors)} surviving devices cannot form a "
                "training mesh (the strict model axes must divide and a "
                "data/fsdp axis must absorb the shrink)",
            )
        from contextlib import nullcontext

        pause = telemetry.pause("elastic_reshard") if telemetry is not None else nullcontext()
        with pause:
            return self._run_ladder(lost_host, lost_ids, survivors, t0, telemetry)

    def _run_ladder(self, lost_host, lost_ids, survivors, t0, telemetry) -> dict:
        tried: list[str] = []
        rung = None
        steps_lost = 0
        scaler_host = self._read_scaler(lost_ids)

        # rung 1: buddy reshard — only a FRESH mirror is usable (a stale one
        # mixed with fresh survivor shards would be a state from two steps),
        # and only when the surviving primary∪buddy shards tile every leaf
        # (checked on sharding metadata, before a byte moves)
        if self.config.redundancy:
            tried.append("buddy")
            if self._buddy_fresh():
                if tree_covered(
                    self.model.params, lost_ids, self._buddy["params"]
                ) and tree_covered(
                    self.optimizer.opt_state, lost_ids, self._buddy["opt_state"]
                ):
                    rung = "buddy"
                else:
                    logger.warning(
                        "elastic: buddy rung failed — a shard and its mirror "
                        "are both on lost devices; falling back to checkpoint."
                    )
            else:
                logger.warning(
                    "elastic: buddy mirror is stale (last refreshed at step "
                    f"{self._mirror_step}, loss at step boundary "
                    f"{self.completed_steps}); falling back to checkpoint."
                )

        ckpt_path = None
        if rung is None and self.config.checkpoint_dir is not None:
            from ..fault_tolerance import latest_valid_checkpoint

            tried.append("checkpoint")
            ckpt_path = latest_valid_checkpoint(self.config.checkpoint_dir)

        if rung is None and ckpt_path is None:
            raise self._fail(
                lost_host, t0, telemetry, tried=tried,
                reason="buddy mirror unavailable and no valid checkpoint found"
                + (f" under {self.config.checkpoint_dir}" if self.config.checkpoint_dir else " (no checkpoint_dir configured)"),
            )

        # the mesh shrinks on every successful rung; state placement differs.
        # The old-mesh arrays stay readable through the rebuild, so the buddy
        # relay reads them leaf by leaf straight onto the new layouts.
        self._rebuild_mesh(survivors)
        self._reshard_layouts()
        if rung == "buddy":
            self._relay_state(lost_ids, self._buddy, scaler_host)
        else:
            rung = "checkpoint"
            steps_lost = self._restore_checkpoint(ckpt_path)
        self._recompile()
        self._buddy = None
        if self.config.redundancy:
            self._mirror()  # stands down by itself when one host remains
        gate = self._contract_gate()
        mttr = time.perf_counter() - t0
        report = {
            "event": "recovered",
            "rung": rung,
            "tried": tried,
            "host": lost_host,
            "lost_devices": sorted(lost_ids),
            "mesh": {axis: int(size) for axis, size in self.mesh.shape.items()},
            "steps_lost": steps_lost,
            "resumed_at_step": self.completed_steps,
            "mttr_s": round(mttr, 4),
        }
        if self.membership is not None:
            # membership transition: mint the next epoch WITHOUT the lost
            # host — from here its writes are fenced out as stale. Store
            # weather here must NOT unwind a recovery that already
            # succeeded in memory: degrade, and mint at the next transition
            try:
                report["epoch"] = self.membership.resolve_loss(
                    lost_host, reason=f"recovered_{rung}"
                )
            except Exception as e:  # noqa: BLE001 - see _membership_degraded
                self._membership_degraded("resolve_loss", e)
        if gate is not None:
            report["contract_gate"] = gate
        if telemetry is not None:
            telemetry.write_record("elastic", report)
        self.last_recovery = report
        self.recoveries.append(report)
        logger.warning(
            f"elastic: recovered from host {lost_host} loss via {rung} rung in "
            f"{mttr:.2f}s on mesh {dict(self.mesh.shape)} ({steps_lost} steps lost)"
        )
        return report

    def _fail(self, lost_host, t0, telemetry, tried, reason) -> ElasticFailure:
        record = {
            "event": "recovery_failed",
            "rung": "fail",
            "tried": tried,
            "host": lost_host,
            "reason": reason,
            "mttr_s": round(time.perf_counter() - t0, 4),
        }
        if telemetry is not None:
            telemetry.write_record("elastic", record)
        self.last_recovery = record
        self.recoveries.append(record)
        return ElasticFailure(
            f"elastic recovery from host {lost_host} loss failed after trying "
            f"{tried or ['nothing']}: {reason}. The run cannot continue "
            "correctly — restart from the last checkpoint, or enable "
            "ElasticConfig(redundancy=1) / a checkpoint_dir for in-memory recovery."
        )

    def _read_scaler(self, lost_ids) -> Optional[dict]:
        if self.optimizer.scaler is None:
            return None
        # replicated scalars: every survivor holds a full copy
        return {
            "scale": assemble_from_survivors(self.optimizer.scale, lost_ids),
            "growth_tracker": assemble_from_survivors(self.optimizer.growth_tracker, lost_ids),
        }

    # -- relayout onto the current (shrunken or regrown) mesh -----------------

    def _rebuild_mesh(self, devices: list) -> None:
        state = self.accelerator.state
        new_par = self._shrunk_parallelism(len(devices))
        if new_par is None:
            raise ElasticFailure(
                f"internal: {len(devices)} devices cannot form a training "
                "mesh (feasibility must be checked before the ladder runs)"
            )
        # the rejoin seam: a pure rebuild_mesh under the single controller;
        # on a real multi-controller pod the env-gated path re-initializes
        # jax.distributed over the new member set first (state.py)
        state._partial.rejoin(devices=devices, parallelism=new_par)
        # ZeRO eligibility changes with the mesh (data=1 after a shrink has
        # nothing to shard over); keep the accelerator's resolution honest
        from ..parallel.zero import zero_eligible

        self.accelerator._zero_update_sharding = (
            zero_eligible(state.mesh, self.accelerator.fsdp_plugin)
            and new_par.zero_stage != 0
        )

    def _reshard_layouts(self) -> None:
        """Recompute params/optimizer shardings for the CURRENT mesh — the
        same derivation prepare_model/prepare_optimizer ran, so the layouts
        (and the reshard itself) stay on the PR 11 bit-exact path."""
        from ..parallel.sharding import (
            abstract_like,
            infer_shardings,
            shardings_like,
            zero_update_shardings,
        )

        accelerator = self.accelerator
        mesh = accelerator.mesh
        params_struct = abstract_like(self.model.params)
        rules = accelerator._partition_rules(self.model.module)
        shardings = infer_shardings(params_struct, mesh, rules)
        if accelerator._zero_update_sharding:
            shardings = zero_update_shardings(params_struct, shardings, mesh)
        self.model.params_shardings = shardings
        optimizer = self.optimizer
        optimizer._params_shardings = shardings
        # ZeRO stage 1/2: params replicated but the MOMENTS shard over fsdp —
        # the same opt_reference_shardings derivation prepare_optimizer ran
        # (dropping it here would silently re-replicate the optimizer state,
        # N× its HBM, after a recovery)
        opt_reference = shardings
        plugin = accelerator.fsdp_plugin
        if plugin is not None and plugin.stage < 3:
            opt_reference = infer_shardings(
                params_struct, mesh, rules.with_fsdp_applied()
            )
        state_shapes = jax.eval_shape(optimizer.tx.init, params_struct)
        optimizer._opt_state_shardings = shardings_like(
            state_shapes, params_struct, opt_reference, mesh
        )
        optimizer._opt_state_device_shardings = optimizer._opt_state_shardings
        # in-flight accumulation (if any) lived on the old mesh — drop it;
        # the step boundary means no gradients are pending by contract
        optimizer._grads = None
        optimizer._accum_count = 0
        optimizer._fingerprint_memo = None
        optimizer._zeros_fn_memo = None
        # the scaler scalars are NOT re-placed here: reading the live array
        # could touch a lost device. The buddy relay re-places them from the
        # survivor-read copy; the checkpoint rung's load_state_dict resets
        # them from the manifest.
        # the guard's device state + LKG snapshot live on the old mesh: disarm
        # so the next guarded step re-arms on the new one
        guard = getattr(getattr(accelerator, "resilience", None), "guard", None)
        if guard is not None:
            guard.state = None
            guard._bound = None
            if hasattr(guard, "_snapshot"):
                guard._snapshot = None

    def _relay_state(self, lost_ids: set, buddy: Optional[dict], scaler_host) -> None:
        """Move params + optimizer state from the (old-mesh) surviving shards
        onto the freshly derived layouts through the redistribution primitive
        (parallel/redistribute.py). The plan decides the rung before a byte
        moves: a shrink (lost devices / buddy merge) takes the host-relay
        rung — survivors-only reads, exactly the old per-leaf relay — while
        ``regrow``'s pure relayout (nothing lost) takes the staged path with
        bounded per-chip scratch. The commit is epoch-fenced when membership
        is attached: a zombie coordinator's relay is refused, never applied."""
        from ..parallel.redistribute import EpochFence, redistribute
        from ..parallel.sharding import replicated

        fence = None
        if self.membership is not None:
            fence = EpochFence(self.membership.store, self.membership.epoch)
        fault_plan = getattr(
            getattr(self.accelerator, "resilience", None), "chaos", None
        )
        telemetry = getattr(self.accelerator, "telemetry", None)
        self.model.params = redistribute(
            self.model.params,
            self.model.params_shardings,
            lost_device_ids=lost_ids,
            buddy_tree=buddy["params"] if buddy else None,
            fault_plan=fault_plan,
            epoch_fence=fence,
            telemetry=telemetry,
        )
        self.optimizer.opt_state = redistribute(
            self.optimizer.opt_state,
            self.optimizer._opt_state_device_shardings,
            lost_device_ids=lost_ids,
            buddy_tree=buddy["opt_state"] if buddy else None,
            fault_plan=fault_plan,
            epoch_fence=fence,
            telemetry=telemetry,
        )
        if scaler_host is not None:
            rep = replicated(self.mesh)
            self.optimizer.scale = jax.device_put(scaler_host["scale"], rep)
            self.optimizer.growth_tracker = jax.device_put(
                scaler_host["growth_tracker"], rep
            )

    def _restore_checkpoint(self, path: str) -> int:
        """The checkpoint rung: load the newest valid checkpoint onto the
        (already shrunken) mesh — load_state reshards onto the live layouts,
        the path PR 11 pinned bit-exact — and rewind the coordinator's step
        counter + any prepared dataloaders to the checkpointed positions."""
        from ..fault_tolerance import checkpoint_step

        self.accelerator.load_state(path)
        ckpt_step = checkpoint_step(path)
        steps_lost = max(self.completed_steps - ckpt_step, 0)
        self.completed_steps = ckpt_step
        # dataloader rewind: the prepared loaders re-partition automatically
        # (live data_sharding); their POSITION is the checkpoint's business —
        # a CheckpointManager-driven loop replays via resumed_loader exactly
        # like a cold resume (docs/fault_tolerance.md), so no example is
        # skipped or repeated across the rung.
        telemetry = getattr(self.accelerator, "telemetry", None)
        if telemetry is not None and telemetry.enabled:
            telemetry.goodput.mark_restart()
        return steps_lost

    def _recompile(self) -> None:
        self._step = self.accelerator.compiled_step(
            self._loss_fn, model=self.model, **self._step_kwargs
        )

    def _contract_gate(self) -> Optional[dict]:
        """Run the PR 8 differential gate + replication audit over the
        resharded step (needs a batch shape — stashed from the last step;
        skipped before the first). Analyzer ERRORs raise: resuming on a
        program that fails its own audit would trade a loud failure for a
        silent one."""
        if self.config.contracts_dir is None or self._batch_struct is None:
            return None
        report = self.accelerator.analyze(
            step=self._step,
            batch=self._batch_struct,
            label="elastic_resharded_step",
            write_record=False,
            contracts_dir=self.config.contracts_dir,
        )
        if report.errors:
            raise ElasticFailure(
                "elastic: the resharded step failed its program audit:\n"
                + report.render()
            )
        return {
            "errors": 0,
            "warnings": len(report.warnings),
            "findings": len(report.findings),
        }

    # -- regrow ---------------------------------------------------------------

    def regrow(self, hosts: Optional[list] = None) -> dict:
        """Revived host(s) rejoin: reshard the LIVE survivor state onto the
        regrown mesh (nothing is lost, so this is a pure relayout — the same
        path as the shrink, read from every current shard) and recompile.
        Default revives every lost host (back to the full mesh)."""
        t0 = time.perf_counter()
        revive = set(hosts) if hosts is not None else set(self.lost_hosts)
        if not revive:
            return {"event": "regrown", "hosts": [], "mttr_s": 0.0}
        unknown = revive - self.lost_hosts
        if unknown:
            raise ValueError(f"cannot regrow hosts {sorted(unknown)}: not lost")
        # everything on the CURRENT mesh is readable (nothing lost): the same
        # per-leaf relay, reading every shard, placing onto the grown layouts
        scaler_host = self._read_scaler(set())
        self.lost_hosts -= revive
        self._rebuild_mesh(self.surviving_devices())
        self._reshard_layouts()
        self._relay_state(set(), None, scaler_host)
        self._recompile()
        if self.config.redundancy:
            self._mirror()
        gate = self._contract_gate()
        report = {
            "event": "regrown",
            "hosts": sorted(revive),
            "mesh": {axis: int(size) for axis, size in self.mesh.shape.items()},
            "resumed_at_step": self.completed_steps,
            "mttr_s": round(time.perf_counter() - t0, 4),
        }
        if self.membership is not None:
            # re-admission: one epoch mint per revived host (clears its join
            # record; the host's next heartbeat adopts the new epoch). Store
            # weather degrades — the regrown mesh is already live, and an
            # unadmitted join record re-lists at the next boundary
            for host in sorted(revive):
                try:
                    report["epoch"] = self.membership.admit(host)
                except Exception as e:  # noqa: BLE001 - see _membership_degraded
                    self._membership_degraded("admit", e)
        if gate is not None:
            report["contract_gate"] = gate
        telemetry = getattr(self.accelerator, "telemetry", None)
        if telemetry is not None and telemetry.enabled:
            telemetry.write_record("elastic", report)
        self.recoveries.append(report)
        logger.info(
            f"elastic: regrew hosts {sorted(revive)} onto mesh {dict(self.mesh.shape)} "
            f"in {report['mttr_s']:.2f}s"
        )
        return report
