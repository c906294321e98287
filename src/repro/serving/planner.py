"""AutoPlanner — model → time matrix → DSE → running server, in one call.

The paper's deployment story is a chain of artifacts: layer descriptors
(Eq. 3-4) feed the Eq. 5/8 performance model, which fills the time matrix
``T[layer][stage_config]`` (Eq. 10's inputs); Algorithms 1-3 search the
design space (size per Eq. 2) for the plan maximising Eq. 12 throughput;
the runtime then executes that plan.  The repo had every link of that
chain as a separate module — this planner composes them so

    server = serve("squeezenet")

is the whole pipeline: build graph → predict times → ``pipe_it_search``
→ :class:`~repro.serving.server.PipelineServer`, warmed and started.

Time sources
------------
``source="synthetic"``  — :func:`repro.core.calibration.synthetic_model`:
    deterministic analytical timings; fast, reproducible, used in tests.
``source="calibrated"`` — :func:`repro.core.calibration.calibrate`: fits
    Eq. 5/8 to GEMMs measured on *this* host (cached after the first run).
An explicit ``time_matrix`` overrides both (the benchmarks inject their
simulated-board matrices this way).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Sequence, Union

import jax

from ..cnn.graph import Graph
from ..cnn.models import MODELS
from ..core.calibration import calibrate, synthetic_model
from ..core.dse import (
    PartitionPlan,
    PowerAwarePlan,
    partition_search,
    pipe_it_search,
    power_aware_search,
)
from ..core.perfmodel import LayerTimePredictor
from ..core.pipeline import PipelinePlan, TimeMatrix
from ..core.platform import CoreType, HeteroPlatform, hikey970
from .adaptive import AdaptiveConfig, attach_adaptive
from .governor import attach_governor
from .multimodel import MultiModelServer, attach_partition_adaptive
from .registry import ModelRegistry
from .server import PipelineServer


def host_platform(n_groups: int = 2) -> HeteroPlatform:
    """This shared-CPU container seen as a pipeline platform.

    ``n_groups`` equal-speed single-"core" clusters whose concurrency XLA
    inter-op threading provides (DESIGN.md §2).  Planning against this
    platform with ``source="calibrated"`` balances the stages in *host*
    time — which is what actually maximises ``PipelineServer`` throughput
    here, the same way the paper's board-measured matrix does on the
    HiKey-970.
    """
    if not 1 <= n_groups <= 8:
        raise ValueError("n_groups must be in [1, 8]")
    return HeteroPlatform(
        name=f"host{n_groups}",
        core_types=tuple(
            CoreType(chr(ord("L") + i), 1, 1.0) for i in range(n_groups)
        ),
    )


@dataclasses.dataclass
class AutoPlanner:
    """End-to-end plan construction for a CNN graph.

    mode : DSE mode — "merge" (the paper's Algorithm 3), "sweep"
        (beyond-paper work_flow-over-all-pipelines, DESIGN.md §2) or
        "best" (both, keep the higher-throughput plan).
    source : where predicted layer times come from (see module docstring).
    backend : kernel route of the stage executables ("xla" |
        "pallas_fused" | resolved ``KernelBackend``); threaded into
        ``build_stage_fns``.
    measured : {autotuner descriptor key: seconds} route measurements
        (``measure_graph_routes``); they override the Eq. 5 regression in
        the predictor (``LayerTimePredictor(measured=...)``) so the time
        matrix reflects the kernels that actually serve.
    tuner : a ``repro.kernels.autotune.ConvAutotuner``; fallback source
        of ``measured`` (all-route merge) when no explicit mapping is
        given.
    """

    platform: HeteroPlatform = dataclasses.field(default_factory=hikey970)
    mode: str = "best"
    source: str = "synthetic"
    backend: object = None
    measured: object = None
    tuner: object = None

    def predictor(self) -> LayerTimePredictor:
        if self.source == "synthetic":
            model = synthetic_model()
        elif self.source == "calibrated":
            model = calibrate()
        else:
            raise ValueError(f"unknown time source {self.source!r}")
        measured = self.measured
        if measured is None and self.tuner is not None:
            measured = self.tuner.route_seconds()
        return LayerTimePredictor(
            model=model, platform=self.platform, measured=measured
        )

    def time_matrix(self, graph: Graph) -> TimeMatrix:
        """Predicted T[layer][stage_config] for the graph's major layers."""
        return self.predictor().time_matrix(graph.descriptors())

    def search(self, n_layers: int, T: TimeMatrix) -> PipelinePlan:
        """Run the DSE on an existing time matrix (Algorithms 1-3)."""
        return pipe_it_search(n_layers, self.platform, T, mode=self.mode)

    def plan(self, graph: Graph, T: Optional[TimeMatrix] = None) -> PipelinePlan:
        T = self.time_matrix(graph) if T is None else T
        return self.search(len(graph.descriptors()), T)

    def power_plan(
        self,
        graph: Graph,
        T: Optional[TimeMatrix] = None,
        *,
        power_cap_w: Optional[float] = None,
        objective: str = "throughput",
        min_throughput: Optional[float] = None,
    ) -> PowerAwarePlan:
        """The DVFS-extended DSE: plan + per-stage OPP assignment under an
        average-power cap (:func:`repro.core.dse.power_aware_search`)."""
        T = self.time_matrix(graph) if T is None else T
        return power_aware_search(
            len(graph.descriptors()), self.platform, T, mode=self.mode,
            power_cap_w=power_cap_w, objective=objective,
            min_throughput=min_throughput,
        )

    # ------------------------------------------------------- multi-model path
    def time_matrices(
        self, graphs: Mapping[str, Graph]
    ) -> Dict[str, TimeMatrix]:
        """Per-model predicted time matrices with one shared per-geometry
        memo (co-resident zoo CNNs share many conv shapes)."""
        return self.predictor().time_matrices(
            {name: g.descriptors() for name, g in graphs.items()}
        )

    def partition(
        self,
        graphs: Mapping[str, Graph],
        Ts: Optional[Mapping[str, TimeMatrix]] = None,
        *,
        weights: Optional[Mapping[str, float]] = None,
        slo_rates: Optional[Mapping[str, float]] = None,
        exact_threshold: int = 8,
        fairness: str = "sum",
        power_cap_w: Optional[float] = None,
        power_objective: str = "throughput",
    ) -> PartitionPlan:
        """Two-level DSE: clusters across models, layers within each share
        (:func:`repro.core.dse.partition_search`)."""
        if Ts is None:
            Ts = self.time_matrices(graphs)
        return partition_search(
            {name: Ts[name] for name in graphs},  # graph order defines model order
            self.platform,
            weights=weights,
            slo_rates=slo_rates,
            mode=self.mode,
            exact_threshold=exact_threshold,
            fairness=fairness,
            power_cap_w=power_cap_w,
            power_objective=power_objective,
        )

    def build_multi(
        self,
        registry: ModelRegistry,
        *,
        time_matrices: Optional[Mapping[str, TimeMatrix]] = None,
        batch_size: int = 1,
        flush_timeout_s: float = 0.01,
        queue_depth: int = 2,
        max_inflight=None,
        warmup: bool = True,
        stage_fn_builders=None,
        fairness: str = "sum",
        power_cap_w: Optional[float] = None,
        power_objective: str = "throughput",
        partition: Optional[PartitionPlan] = None,
        recovery=None,
    ) -> MultiModelServer:
        """Partition the platform across the registry's models and
        construct a (warmed, started) :class:`MultiModelServer`.

        ``partition`` overrides the two-level DSE (the ``resume_from``
        warm-start path hands a persisted partition in here); ``recovery``
        arms every inner pipeline's fault-recovery layer
        (:class:`~repro.serving.faults.RecoveryPolicy`)."""
        if partition is None:
            partition = self.partition(
                registry.graphs(),
                time_matrices,
                weights=registry.weights(),
                slo_rates=registry.slo_rates(),
                fairness=fairness,
                power_cap_w=power_cap_w,
                power_objective=power_objective,
            )
        mserver = MultiModelServer(
            registry,
            partition,
            batch_size=batch_size,
            flush_timeout_s=flush_timeout_s,
            queue_depth=queue_depth,
            max_inflight=max_inflight,
            stage_fn_builders=stage_fn_builders,
            backend=self.backend,
            tuner=self.tuner,
            fairness=fairness,
            recovery=recovery,
        )
        if warmup:
            mserver.warmup()
        return mserver.start()

    def build(
        self,
        graph: Graph,
        params=None,
        *,
        time_matrix: Optional[TimeMatrix] = None,
        batch_size: int = 4,
        flush_timeout_s: float = 0.01,
        queue_depth: int = 2,
        seed: int = 0,
        warmup: bool = True,
        stage_fn_builder=None,
        plan: Optional[PipelinePlan] = None,
        recovery=None,
    ) -> PipelineServer:
        """Plan the pipeline and construct a (warmed, started) server.

        ``plan`` overrides the DSE (the power-aware path plans once via
        :meth:`power_plan` and hands the resulting allocation in here,
        and ``serve(resume_from=)`` a persisted one); ``recovery`` arms
        the fault-recovery layer
        (:class:`~repro.serving.faults.RecoveryPolicy`)."""
        if params is None:
            params = graph.init(jax.random.PRNGKey(seed))
        if plan is None:
            plan = self.plan(graph, time_matrix)
        server = PipelineServer(
            graph,
            params,
            plan,
            batch_size=batch_size,
            flush_timeout_s=flush_timeout_s,
            queue_depth=queue_depth,
            stage_fn_builder=stage_fn_builder,
            backend=self.backend,
            recovery=recovery,
        )
        if warmup:
            server.warmup()
        return server.start()


def serve(
    model: Union[str, Graph, Mapping, ModelRegistry],
    *,
    mode: str = "best",
    source: str = "synthetic",
    platform: Optional[HeteroPlatform] = None,
    time_matrix: Optional[TimeMatrix] = None,
    params=None,
    batch_size: int = 4,
    flush_timeout_s: float = 0.01,
    queue_depth: int = 2,
    seed: int = 0,
    warmup: bool = True,
    adaptive: bool = False,
    adaptive_config: Optional[AdaptiveConfig] = None,
    stage_fn_builder=None,
    backend=None,
    autotune: bool = False,
    tuner=None,
    max_inflight=None,
    fairness: Optional[str] = None,
    power_cap_w: Optional[float] = None,
    power_objective: str = "throughput",
    min_throughput: Optional[float] = None,
    recovery=None,
    plan_store=None,
    resume_from=None,
) -> PipelineServer:
    """One call from model name (or Graph) to a running PipelineServer.

    **Fault tolerance** (serving/faults.py): ``recovery`` — a
    :class:`~repro.serving.faults.RecoveryPolicy` — arms worker-crash
    restart, transient-error retry with backoff, at-least-once ticket
    re-dispatch, and the stall watchdog on the server (or on every inner
    pipeline of a multi-model deployment).  ``plan_store`` (a path or
    :class:`~repro.serving.persistence.PlanStore`) persists the active
    plan as last-known-good JSON on startup and after every successful
    hot-swap; ``resume_from`` (same types, typically the same path)
    restores a persisted plan/partition on restart and SKIPS the cold
    calibrate + DSE path — absent or unusable files fall back to a
    normal cold start.

    **Power-aware serving**: ``power_cap_w`` (watts of modeled average
    active power on the planning platform) and/or
    ``power_objective="throughput_per_watt"`` switch the DSE to the
    DVFS-extended search (:func:`repro.core.dse.power_aware_search`) —
    the plan carries a per-stage OPP assignment, non-bottleneck stages
    are down-clocked to the slack-matched level, and the server gets a
    :class:`~repro.serving.governor.DvfsGovernor` on ``server.governor``
    (``server.governor.throttle(new_cap)`` is the thermal-event entry
    point; with ``adaptive=True`` the control loop also normalizes
    observations through it).  Multi-model: the cap bounds the whole
    machine and each share's inner search runs under its slice.

    With ``adaptive=True`` the server also gets the closed control loop
    of :mod:`repro.serving.adaptive`: a monitor thread calibrates the
    planner's time matrix against observed stage times, and re-plans +
    hot-swaps the layer allocation when the bottleneck drifts
    (``server.monitor`` holds it; ``server.stop()`` shuts it down).

    ``backend`` selects the kernel route of every stage executable:
    "xla", the reference and the default, or "pallas_fused", the fused
    kernels (see :mod:`repro.kernels.backend`).  ``autotune=True`` attaches a
    :class:`repro.kernels.autotune.ConvAutotuner` (or pass an existing
    one via ``tuner``): the tuner measures each layer's serving route
    once (JSON-cached per device kind), picks fused block sizes, and the
    planner's time matrix is built from those measurements instead of
    the Eq. 5 regression alone — so the DSE balances stages by the
    kernels that actually run.

    **Multi-model co-serving**: pass a dict (or
    :class:`~repro.serving.registry.ModelRegistry`) instead of one model
    and ``serve`` returns a :class:`~repro.serving.multimodel.
    MultiModelServer` — the two-level partition DSE splits the clusters
    across the models, one pipeline worker set per model runs on its
    share behind the admission-controlled router, every model's route
    measurements share ONE autotuner cache, and ``adaptive=True``
    attaches the global re-partition loop.  ``max_inflight`` (an int or
    ``{model: bound}``) arms the router's per-model admission bound and
    ``fairness`` ("sum" | "max-min") selects the partition objective —
    both are multi-model-only and rejected for a single model.

    A ticket's result is a read-only ``numpy.ndarray`` of shape
    ``(1, classes)``: a row of the one device-to-host copy the server
    makes of each micro-batch.

    >>> server = serve("squeezenet", mode="best", batch_size=8)
    >>> ticket = server.submit(image)
    >>> logits = ticket.result()  # numpy, (1, classes), read-only
    >>> server.stop()

    >>> mm = serve({"alex": "alexnet", "squeeze": "squeezenet"})
    >>> logits = mm.submit("alex", image).result()
    >>> mm.stop()
    """
    from ..kernels.backend import measure_graph_routes, resolve_backend
    from .persistence import PlanStore

    if isinstance(model, (Mapping, ModelRegistry)):
        if min_throughput is not None:
            raise ValueError(
                "min_throughput is a single-model option; multi-model "
                "throughput floors are per-model SLOs — set slo_rate on the "
                "registry entries instead"
            )
        return _serve_multi(
            ModelRegistry.coerce(model),
            mode=mode,
            source=source,
            platform=platform,
            time_matrix=time_matrix,
            batch_size=batch_size,
            flush_timeout_s=flush_timeout_s,
            queue_depth=queue_depth,
            warmup=warmup,
            adaptive=adaptive,
            adaptive_config=adaptive_config,
            stage_fn_builder=stage_fn_builder,
            backend=backend,
            autotune=autotune,
            tuner=tuner,
            max_inflight=max_inflight,
            fairness=fairness if fairness is not None else "sum",
            power_cap_w=power_cap_w,
            power_objective=power_objective,
            recovery=recovery,
            plan_store=plan_store,
            resume_from=resume_from,
        )
    if max_inflight is not None or fairness is not None:
        raise ValueError(
            "max_inflight/fairness are multi-model options; pass a dict of "
            "models (or a ModelRegistry) to serve()"
        )

    graph = MODELS[model]() if isinstance(model, str) else model
    if tuner is None and autotune:
        from ..kernels.autotune import ConvAutotuner

        tuner = ConvAutotuner()
    kb = resolve_backend(backend, tuner=tuner)
    measured = None
    if tuner is not None and time_matrix is None:
        # skipped when the caller pins an explicit time matrix — the
        # measurements would be dead startup latency
        measured = measure_graph_routes(graph, kb, tuner)
    planner = AutoPlanner(
        platform=platform if platform is not None else hikey970(),
        mode=mode,
        source=source,
        backend=kb,
        measured=measured,
        tuner=tuner,
    )
    # Warm start: a persisted last-known-good plan skips the cold
    # calibrate + DSE path entirely (best effort — an absent or unusable
    # store falls back to a normal cold start).
    resume_plan = None
    if resume_from is not None:
        ir = PlanStore.coerce(resume_from).load_plan()
        if ir is not None:
            resume_plan = ir.as_pipeline_plan()
    # min_throughput alone also arms the power path: the floor is enforced
    # as DVFS-feasibility, never silently dropped
    power_aware = (
        power_cap_w is not None
        or power_objective != "throughput"
        or min_throughput is not None
    )
    # The time matrix is only built when something still needs it: the
    # DSE (no resume), the power-aware frequency search, or the adaptive
    # loop's prior.  A resumed fixed-clock static server skips it.
    need_T = (
        time_matrix is not None
        or resume_plan is None
        or power_aware
        or adaptive
    )
    T = None
    if need_T:
        T = planner.time_matrix(graph) if time_matrix is None else time_matrix
    pplan = None
    if power_aware:
        pplan = planner.power_plan(
            graph, T, power_cap_w=power_cap_w, objective=power_objective,
            min_throughput=min_throughput,
        )
    server = planner.build(
        graph,
        params,
        time_matrix=T,
        batch_size=batch_size,
        flush_timeout_s=flush_timeout_s,
        queue_depth=queue_depth,
        seed=seed,
        warmup=warmup,
        stage_fn_builder=stage_fn_builder,
        plan=(
            pplan.plan if pplan is not None
            else resume_plan if resume_plan is not None
            else None
        ),
        recovery=recovery,
    )
    if power_aware:
        # the governor owns the clocks; its monitor thread only runs when
        # the caller asked for the adaptive loop (throttle() works either way)
        attach_governor(
            server,
            prior=T,
            platform=planner.platform,
            power_cap_w=power_cap_w,
            objective=power_objective,
            min_throughput=min_throughput,
            mode=mode,
            config=adaptive_config,
            start=adaptive,
        )
    elif adaptive:
        attach_adaptive(
            server,
            prior=T,
            platform=planner.platform,
            mode=mode,
            config=adaptive_config,
        )
    if plan_store is not None:
        # After governor attachment so the persisted plan carries the
        # assigned clocks; the startup plan is the first known-good.
        server.plan_store = PlanStore.coerce(plan_store)
        server._persist_plan()
    return server


def _serve_multi(
    registry: ModelRegistry,
    *,
    mode: str,
    source: str,
    platform: Optional[HeteroPlatform],
    time_matrix,
    batch_size: int,
    flush_timeout_s: float,
    queue_depth: int,
    warmup: bool,
    adaptive: bool,
    adaptive_config: Optional[AdaptiveConfig],
    stage_fn_builder,
    backend,
    autotune: bool,
    tuner,
    max_inflight,
    fairness: str,
    power_cap_w: Optional[float] = None,
    power_objective: str = "throughput",
    recovery=None,
    plan_store=None,
    resume_from=None,
) -> MultiModelServer:
    """The multi-model arm of :func:`serve`.

    Mirrors the single-model chain per co-resident model — calibrate,
    predict, search, run — but with the two-level partition DSE in the
    middle and exactly ONE :class:`ConvAutotuner` shared by every model's
    route measurements: descriptor keys are geometry-keyed, so a conv
    shape two models share is measured once and both time matrices see
    the same measured truth.
    """
    from ..kernels.backend import measure_graph_routes, resolve_backend
    from .persistence import PlanStore

    if len(registry) == 0:
        raise ValueError("serve() got an empty model registry")
    if tuner is None and autotune:
        from ..kernels.autotune import ConvAutotuner

        tuner = ConvAutotuner()
    kb = resolve_backend(backend, tuner=tuner)
    measured = None
    if tuner is not None and time_matrix is None:
        for entry in registry:  # one shared cache: common shapes time once
            measure_graph_routes(entry.graph, kb, tuner)
        measured = tuner.route_seconds()
    planner = AutoPlanner(
        platform=platform if platform is not None else hikey970(),
        mode=mode,
        source=source,
        backend=kb,
        measured=measured,
        tuner=tuner,
    )
    # Warm start: a persisted last-known-good partition skips the cold
    # calibrate + two-level DSE path (best effort).
    resume_partition = None
    if resume_from is not None:
        resume_partition = PlanStore.coerce(resume_from).load_partition(
            planner.platform
        )
        if resume_partition is not None and sorted(
            resume_partition.names
        ) != sorted(e.name for e in registry):
            resume_partition = None  # the model zoo changed: cold start
    # Time matrices are only built when something still needs them: the
    # partition DSE (no resume) or the adaptive loop's priors.
    Ts = None
    if time_matrix is None:
        if resume_partition is None or adaptive:
            Ts = planner.time_matrices(registry.graphs())
    elif isinstance(time_matrix, Mapping):
        Ts = {e.name: time_matrix[e.name] for e in registry}
    else:
        raise ValueError(
            "multi-model serve() needs time_matrix as {model: TimeMatrix}"
        )
    builders = None
    if stage_fn_builder is not None:
        # a single builder callable applies to every model; per-model
        # overrides go through AutoPlanner.build_multi directly
        builders = {e.name: stage_fn_builder for e in registry}
    mserver = planner.build_multi(
        registry,
        time_matrices=Ts,
        batch_size=batch_size,
        flush_timeout_s=flush_timeout_s,
        queue_depth=queue_depth,
        warmup=warmup,
        stage_fn_builders=builders,
        max_inflight=max_inflight,
        fairness=fairness,
        power_cap_w=power_cap_w,
        power_objective=power_objective,
        partition=resume_partition,
        recovery=recovery,
    )
    if adaptive:
        attach_partition_adaptive(
            mserver,
            priors=Ts,
            platform=planner.platform,
            mode=mode,
            config=adaptive_config,
            power_cap_w=power_cap_w,
            power_objective=power_objective,
        )
    if plan_store is not None:
        mserver.plan_store = PlanStore.coerce(plan_store)
        mserver._persist_partition()
    return mserver
