"""Kernel execution route for the CNN serving hot path.

Every conv, depthwise and fc node a `Graph` executes goes through one
`KernelBackend`, which holds one decision: the reference route or the
served one.

``"xla"``
    The reference: explicit im2col patch matrix + jnp matmul, and XLA's
    grouped conv for depthwise nodes (`cnn/layers.py`); the activation is
    applied after the op.
``"pallas_fused"``
    The served route: the fused implicit-GEMM kernel
    (`kernels/conv_fused.py`) with the activation in its K-flush, (bm,
    bn, bk) from the `ConvAutotuner` when one is attached; depthwise
    nodes take the depthwise kernel (`kernels/dwconv.py`) and fc nodes
    `matmul_fused`.  Where Pallas is not active (off-TPU, no interpret
    request) each resolves to its fused XLA lowering, `fused_route_ref`.
    Shapes the kernels cannot tile (grouped convs, depthwise shapes
    `dwconv.supports` rejects) go to `fused_route_ref` too and are
    recorded in ``fallbacks``.

`resolve_backend` turns a route name (or None, the reference) into a
`KernelBackend`; everything above `Graph._apply_node` (stage builders,
engines, server, planner) threads that one object through.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax.numpy as jnp

from .autotune import ConvAutotuner
from .config import interpret_requested, on_tpu
from .conv_fused import apply_act, conv2d_fused, fused_route_ref, matmul_fused, supports
from .dwconv import dwconv2d_fused
from .dwconv import supports as dw_supports

BACKENDS = ("xla", "pallas_fused")


def _pallas_active(interpret: Optional[bool]) -> bool:
    """Should the fused *Pallas kernel* itself execute?  On TPU, always
    (and REPRO_PALLAS_INTERPRET asking for the interpreter raises there);
    elsewhere only when interpret mode is explicitly requested (argument
    or REPRO_PALLAS_INTERPRET) — never silently on a serving path.  An
    explicit ``interpret=False`` pins the XLA route off-TPU even under
    the env override."""
    requested = interpret_requested()
    if on_tpu():
        return True
    if interpret is not None:
        return bool(interpret)
    return bool(requested)


@dataclasses.dataclass
class KernelBackend:
    """One route (a name from :data:`BACKENDS`) for every major node, with
    automatic XLA fallback on the served route.

    ``fallbacks`` records nodes the fused kernels declined (shape they
    cannot tile) as ``{node_name: reason}`` — the observability hook the
    grouped/depthwise tests assert on.
    """

    route: str = "xla"
    tuner: Optional[ConvAutotuner] = None
    interpret: Optional[bool] = None
    fallbacks: Dict[str, str] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.route not in BACKENDS:
            raise ValueError(f"unknown backend {self.route!r}; pick from {BACKENDS}")

    def _blocks(self, desc) -> Dict[str, int]:
        if self.tuner is None:
            return {}
        return self.tuner.tune(desc).as_kwargs()

    # -------------------------------------------------------------- convs
    def conv2d(
        self,
        name: str,
        x: jnp.ndarray,
        w: jnp.ndarray,
        b: Optional[jnp.ndarray],
        *,
        stride: int = 1,
        pad: int = 0,
        groups: int = 1,
        act: str = "none",
    ) -> Tuple[jnp.ndarray, bool]:
        """Returns ``(y, act_done)`` — ``act_done`` when the backend fused
        the activation ``act`` ("none", "relu" or "gelu") into the kernel
        epilogue."""
        from ..cnn import layers as L

        if self.route == "xla":
            return L.conv2d(x, w, b, stride=stride, pad=pad, groups=groups), False
        fh, fw, _, _ = w.shape
        if not supports(fh, fw, stride, groups):
            # grouped convolution is the only shape supports() rejects today
            self.fallbacks[name] = f"groups={groups}"
            return (
                fused_route_ref(
                    x, w, b, stride=stride, pad=pad, groups=groups, act=act
                ),
                True,
            )
        if not _pallas_active(self.interpret):
            # fused XLA lowering of the same operation (off-TPU serving)
            return (
                fused_route_ref(x, w, b, stride=stride, pad=pad, act=act),
                True,
            )
        desc = self._desc(name, x, w, stride, pad, groups)
        y = conv2d_fused(
            x, w, b, stride=stride, pad=pad, act=act,
            interpret=self.interpret, **self._blocks(desc),
        )
        return y, True

    def depthwise(
        self,
        name: str,
        x: jnp.ndarray,
        w: jnp.ndarray,
        b: Optional[jnp.ndarray],
        *,
        stride: int = 1,
        pad: int = 0,
        act: str = "none",
    ) -> Tuple[jnp.ndarray, bool]:
        """Under ``pallas_fused``, stride-1 ``same`` convs with an odd
        kernel run the depthwise Pallas kernel (`kernels/dwconv.py`) where
        Pallas is active, and XLA's fused route off-TPU; other shapes keep
        XLA's grouped conv with the epilogue fused, and are recorded in
        ``fallbacks``.  The reference uses the native grouped conv
        (ARM-CL special-cases depthwise the same way)."""
        from ..cnn import layers as L

        if self.route == "xla":
            return L.depthwise_conv2d(x, w, b, stride=stride, pad=pad), False
        _, h, wd, c = x.shape
        if not dw_supports(h, wd, c, w.shape[0], stride, pad):
            self.fallbacks[name] = "depthwise"
        elif _pallas_active(self.interpret):
            y = dwconv2d_fused(
                x, w, b, stride=stride, pad=pad, act=act, interpret=self.interpret
            )
            return y, True
        return (
            fused_route_ref(
                x, w, b, stride=stride, pad=pad, groups=c, act=act,
            ),
            True,
        )

    # -------------------------------------------------------------- dense
    def dense(
        self,
        name: str,
        x: jnp.ndarray,
        w: jnp.ndarray,
        b: Optional[jnp.ndarray],
        *,
        act: str = "none",
    ) -> Tuple[jnp.ndarray, bool]:
        from ..cnn import layers as L

        if self.route == "xla":
            return L.dense(x, w, b), False
        x2 = x.reshape(x.shape[0], -1)
        bias = jnp.zeros((w.shape[1],), jnp.float32) if b is None else b
        if not _pallas_active(self.interpret):
            # XLA fuses the epilogue into the GEMM
            return apply_act(x2 @ w + bias, act), True
        return (
            matmul_fused(x2, w, bias, act=act, interpret=self.interpret),
            True,
        )

    # ------------------------------------------------------------- helpers
    @staticmethod
    def _desc(name, x, w, stride, pad, groups):
        from ..core.descriptors import ConvDescriptor

        fh, fw, _, cout = w.shape
        return ConvDescriptor(
            name=name, i_w=x.shape[2], i_h=x.shape[1], i_d=x.shape[3],
            f_w=fw, f_h=fh, ofm=cout, pad=pad, stride=stride, groups=groups,
        )


def resolve_backend(
    spec: Union[None, str, KernelBackend],
    *,
    tuner: Optional[ConvAutotuner] = None,
    interpret: Optional[bool] = None,
) -> KernelBackend:
    """A resolved backend passes through; a route name, or None for the
    ``"xla"`` reference, builds one."""
    if isinstance(spec, KernelBackend):
        return spec
    route = "xla" if spec is None else spec
    return KernelBackend(route, tuner=tuner, interpret=interpret)


def finish_act(result: Tuple[jnp.ndarray, bool]) -> jnp.ndarray:
    """Apply the ReLU a backend did NOT fuse — keeps cross-backend timing
    and parity comparisons symmetric (same total work on every route)."""
    y, act_done = result
    return y if act_done else jnp.maximum(y, 0.0)


def measure_graph_routes(
    graph, kb: KernelBackend, tuner: ConvAutotuner, batch: int = 1
) -> Dict[str, float]:
    """Measure (best-of-k, JSON-cached per route name) the serving-route
    seconds of every major layer of ``graph`` under backend ``kb`` —
    single image, single stream, the paper's T-matrix measurement unit.
    Returns {descriptor key: seconds} for exactly the routes this backend
    selects — the mapping `LayerTimePredictor` consumes.
    """
    import jax
    import numpy as np

    rng = np.random.default_rng(0)
    measured: Dict[str, float] = {}

    def timed(desc, fn):
        from .autotune import descriptor_key

        measured[descriptor_key(desc)] = tuner.measure_route(
            desc, lambda: jax.block_until_ready(fn()), route=kb.route
        )

    for desc in graph.descriptors():
        if desc.kind == "fc":
            k, m = desc.i_w * desc.i_h * desc.i_d, desc.ofm
            x = jnp.asarray(rng.standard_normal((batch, k)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((k, m)) * 0.02, jnp.float32)
            b = jnp.zeros((m,), jnp.float32)
            timed(
                desc,
                lambda x=x, w=w, b=b, n=desc.name: finish_act(
                    kb.dense(n, x, w, b, act="relu")
                ),
            )
        elif desc.kind == "depthwise":
            x = jnp.asarray(
                rng.standard_normal((batch, desc.i_h, desc.i_w, desc.i_d)), jnp.float32
            )
            w = jnp.asarray(
                rng.standard_normal((desc.f_h, desc.f_w, 1, desc.i_d)) * 0.1, jnp.float32
            )
            b = jnp.zeros((desc.i_d,), jnp.float32)
            timed(
                desc,
                lambda x=x, w=w, b=b, d=desc: finish_act(
                    kb.depthwise(d.name, x, w, b, stride=d.stride, pad=d.pad, act="relu")
                ),
            )
        else:
            x = jnp.asarray(
                rng.standard_normal((batch, desc.i_h, desc.i_w, desc.i_d)), jnp.float32
            )
            w = jnp.asarray(
                rng.standard_normal((desc.f_h, desc.f_w, desc.f_d, desc.ofm)) * 0.05,
                jnp.float32,
            )
            b = jnp.zeros((desc.ofm,), jnp.float32)
            timed(
                desc,
                lambda x=x, w=w, b=b, d=desc: finish_act(
                    kb.conv2d(
                        d.name, x, w, b, stride=d.stride, pad=d.pad,
                        groups=d.groups, act="relu",
                    )
                ),
            )
    return measured
