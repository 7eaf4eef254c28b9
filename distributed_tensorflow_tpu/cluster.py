"""Cluster bootstrap: the reference's ClusterSpec/Server layer, TPU-native.

Reference behavior (``MNISTDist.py:94-107``): split ``--ps_hosts`` /
``--worker_hosts``, build a two-job ClusterSpec, start a gRPC server for the
local task, then demux on role (ps blocks in ``server.join()``; worker
builds the graph). The same script runs once per task — SPMD by hand.

TPU-native mapping:
- sync mode, multi-host: ``jax.distributed.initialize`` — worker 0's host
  is the coordinator (derived from ``--worker_hosts``); all hosts join one
  global device mesh; there is no ps job at all.
- ps-emulation mode: the host lists keep their exact reference meaning —
  ps tasks run the parameter service (the ``server.join()`` equivalent),
  workers train against it (see ``parallel/ps_emulation.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class ClusterSpec:
    """Static job->hosts membership (tf.train.ClusterSpec parity)."""

    jobs: dict[str, list[str]] = field(default_factory=dict)

    @classmethod
    def from_flags(cls, FLAGS) -> "ClusterSpec":
        ps = [h for h in FLAGS.ps_hosts.split(",") if h]
        workers = [h for h in FLAGS.worker_hosts.split(",") if h]
        return cls({"ps": ps, "worker": workers})

    @property
    def ps_hosts(self) -> list[str]:
        return self.jobs.get("ps", [])

    @property
    def worker_hosts(self) -> list[str]:
        return self.jobs.get("worker", [])

    def task_address(self, job: str, index: int) -> str:
        hosts = self.jobs.get(job, [])
        if not 0 <= index < len(hosts):
            raise ValueError(
                f"task_index {index} out of range for job {job!r} with "
                f"{len(hosts)} hosts"
            )
        return hosts[index]

    def num_tasks(self, job: str) -> int:
        return len(self.jobs.get(job, []))


# ---------------------------------------------------------- membership
#
# The elastic-training world registry (r15): which members of the
# launch-time world are CURRENTLY in it, and the monotonically
# increasing epoch every membership change advances. Single-process
# runs treat each local device as a world member ("device-hosts" — the
# virtual topology the CPU test mesh already simulates); multi-process
# runs treat each process as a member and re-form the runtime through
# ``maybe_initialize_distributed`` at the new size. The registry lives
# HERE because membership is cluster state: ``parallel.mesh.make_mesh``
# consults ``active_devices`` so every mesh any loop builds covers
# exactly the current world, and ``training/elastic.py`` drives the
# transitions.

# hosts: tuple[int] | None = full launch world. Member ids are LAUNCH
# ids and stay stable across resizes — after a multi-host re-form the
# runtime renumbers process indices 0..P-1, so the launch topology
# (worker list + this process's launch id) is recorded here and every
# membership decision maps through it instead of the shifting ranks.
_MEMBERSHIP = {"epoch": 0, "hosts": None, "self_host": None,
               "launch_workers": None}


def reset_membership() -> None:
    """Back to the full launch-time world at epoch 0 (run entry, tests)."""
    _MEMBERSHIP["epoch"] = 0
    _MEMBERSHIP["hosts"] = None
    _MEMBERSHIP["self_host"] = None
    _MEMBERSHIP["launch_workers"] = None


def set_launch_topology(workers, self_host: int) -> None:
    """Record the immutable launch worker list and THIS process's
    launch member id (train()'s elastic wrapper calls this at run
    entry). Survivor re-forms resolve addresses and self-identity
    against these, never against the post-resize renumbering."""
    _MEMBERSHIP["launch_workers"] = tuple(workers or ())
    _MEMBERSHIP["self_host"] = int(self_host)


def launch_workers() -> tuple:
    return _MEMBERSHIP["launch_workers"] or ()


def self_host(default: int = 0) -> int:
    """This process's LAUNCH member id (stable across resizes)."""
    sh = _MEMBERSHIP["self_host"]
    return int(sh) if sh is not None else int(default)


def membership_epoch() -> int:
    return _MEMBERSHIP["epoch"]


def set_world(hosts, epoch: int | None = None) -> int:
    """Install a new membership: ``hosts`` are world-member indices
    (device slots single-process, process ids multi-process); ``epoch``
    defaults to the next one. Returns the installed epoch."""
    hosts = tuple(sorted(int(h) for h in hosts))
    if not hosts:
        raise ValueError("membership change would empty the world — the "
                         "last member cannot be preempted")
    _MEMBERSHIP["hosts"] = hosts
    _MEMBERSHIP["epoch"] = (int(epoch) if epoch is not None
                            else _MEMBERSHIP["epoch"] + 1)
    return _MEMBERSHIP["epoch"]


def world_hosts(default_size: int) -> tuple:
    """Current member indices (``default_size`` fills in the launch
    world when no membership change has happened yet)."""
    hosts = _MEMBERSHIP["hosts"]
    return hosts if hosts is not None else tuple(range(default_size))


def active_devices():
    """The devices the current world owns — what ``make_mesh`` builds
    over. Multi-process worlds resize by re-initializing the runtime
    (every process then sees the survivors' devices as jax.devices()),
    so the filter applies only to the single-process device-host
    topology."""
    import jax

    devs = jax.devices()
    hosts = _MEMBERSHIP["hosts"]
    if hosts is None or jax.process_count() > 1:
        return devs
    bad = [h for h in hosts if h >= len(devs)]
    if bad:
        raise ValueError(
            f"world members {bad} exceed the {len(devs)} local devices "
            f"(--world_size larger than the host?)")
    return [devs[h] for h in hosts]


def resolve_mode(FLAGS) -> str:
    """Demux --mode=auto: reference-style role launch (--ps_hosts set) means
    ps emulation; otherwise sync DP over local devices."""
    mode = FLAGS.mode
    if mode != "auto":
        return mode
    if FLAGS.ps_hosts:
        return "ps"
    if len([h for h in FLAGS.worker_hosts.split(",") if h]) > 1:
        return "sync"
    return "local"


def _initialize_with_retry(init_fn, *, retries: int, backoff_s: float,
                           what: str, sleep=None, cleanup_fn=None) -> None:
    """Bounded retry/backoff around a cluster-join callable.

    The crash-restart recovery path: a worker relaunched after a crash
    reaches ``jax.distributed.initialize`` while the coordinator (worker
    0's host) is itself still coming back — without retry the relaunch
    dies immediately on connection-refused and the recovery story ends
    there. Backoff is linear (attempt x ``backoff_s``, capped at 30 s);
    the final attempt re-raises, so a genuinely dead coordinator still
    fails loudly after a bounded wait. ``sleep`` is injectable for
    tests; the ``init`` fault point fires inside the loop, so
    ``--fault_spec init:mode=refuse:times=2`` proves the retry path
    deterministically."""
    import time

    from distributed_tensorflow_tpu.utils.faults import fault_point

    sleep = sleep or time.sleep
    for attempt in range(retries + 1):
        try:
            fault_point("init", attempt=attempt)
            init_fn()
            return
        except (TypeError, ValueError, KeyError, AttributeError,
                AssertionError):
            # deterministic misconfiguration (bad address string, API
            # misuse) — retrying would just serve the same error after
            # the full backoff schedule; stay loud and fast
            raise
        except Exception as e:  # noqa: BLE001 — connection-class errors
            if attempt >= retries:
                raise
            if cleanup_fn is not None:
                cleanup_fn()
            delay = min(backoff_s * (attempt + 1), 30.0)
            print(f"{what} failed (attempt {attempt + 1}/{retries + 1}: "
                  f"{type(e).__name__}: {e}); coordinator may still be "
                  f"relaunching — retrying in {delay:.1f}s", flush=True)
            sleep(delay)


def _epoch_coordinator(coordinator: str, epoch: int) -> str:
    """Namespace the coordination service by membership epoch: the port
    offsets by ``epoch``, so a stale peer still dialing (or holding) the
    previous epoch's service can never join — or wedge — the re-formed
    world. Epoch 0 is byte-identical to the pre-elastic behavior."""
    if not epoch:
        return coordinator
    host, _, port = coordinator.rpartition(":")
    if not host or not port.isdigit():
        return coordinator
    return f"{host}:{int(port) + int(epoch)}"


def maybe_initialize_distributed(cluster: ClusterSpec, task_index: int,
                                 init_retries: int = 0,
                                 init_backoff_s: float = 2.0,
                                 init_timeout_s: float = 0.0,
                                 membership_epoch: int = 0) -> bool:
    """Multi-host sync mode: join the JAX coordination service over DCN.

    Worker 0's host acts as coordinator (the role the chief's master service
    plays in the reference). Single-host runs skip this entirely. Returns
    True if distributed init happened.

    ``init_retries`` > 0 arms the crash-restart recovery path: a worker
    relaunched after a crash retries the join with linear backoff
    (``init_backoff_s``) while the coordinator comes back, instead of
    dying on the first connection refusal. ``init_timeout_s`` > 0 caps
    each attempt's in-library wait (jax's ``initialization_timeout``,
    default 300 s) so retry attempts turn over fast enough to matter.

    ``membership_epoch`` > 0 is the elastic re-form path (training/
    elastic.py): survivors of a membership change re-initialize at the
    new world size against an epoch-namespaced coordination service
    (``_epoch_coordinator`` offsets the port), so a stale peer from the
    previous epoch cannot race the re-formed cluster; every retry/
    backoff line names the epoch so interleaved relaunch logs stay
    attributable.
    """
    workers = cluster.worker_hosts
    if len(workers) <= 1:
        return False
    import jax

    # CPU multi-process (the distributed-without-a-cluster test topology,
    # SURVEY.md §4): newer jaxlib defaults the CPU collectives
    # implementation to "none", which turns every cross-host psum into
    # "Multiprocess computations aren't implemented on the CPU backend".
    # Opt into gloo BEFORE backend init; real TPU platforms are untouched.
    if (jax.config.jax_platforms or "").startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator = _epoch_coordinator(workers[0],
                                     int(membership_epoch or 0))
    kwargs = dict(
        coordinator_address=coordinator,
        num_processes=len(workers),
        process_id=task_index,
    )
    if init_timeout_s and init_timeout_s > 0:
        kwargs["initialization_timeout"] = int(init_timeout_s)

    def _init():
        jax.distributed.initialize(**kwargs)

    def _cleanup():
        # a failed connect leaves global_state.client set; a bare retry
        # would then raise "should only be called once" — tear the
        # half-initialized state down first (best-effort on every field)
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — half-connected client
            pass
        state = getattr(jax.distributed, "global_state", None)
        if state is not None:
            for field_name in ("client", "service",
                               "preemption_sync_manager"):
                try:
                    setattr(state, field_name, None)
                except Exception:  # noqa: BLE001
                    pass

    import time

    from distributed_tensorflow_tpu.utils import telemetry

    epoch_tag = (f" [membership epoch {int(membership_epoch)}]"
                 if membership_epoch else "")
    with telemetry.trace_span("cluster_init", coordinator=coordinator,
                              process=int(task_index),
                              epoch=int(membership_epoch or 0)):
        _initialize_with_retry(
            _init, retries=max(0, int(init_retries)),
            backoff_s=float(init_backoff_s),
            what=f"jax.distributed.initialize({coordinator}){epoch_tag}",
            cleanup_fn=_cleanup)
    # every process leaves initialize() once the coordinator has all
    # members — a coarse first clock anchor for the fleet timeline
    # (refined by the coord_clock markers at every vote); rides the
    # span ring + flight recorder even before a sink is configured
    telemetry.get_tracer().record_instant(
        "init_clock", process=int(task_index), mono=time.monotonic())
    return True
