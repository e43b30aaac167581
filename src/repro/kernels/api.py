"""Unified kernel-dispatch API: the paper's ACCEL/HOST control law as an
executable router.

``core.offload.plan_offload`` decides *analytically* which kernels fit
the LMM/VMEM budget; this module makes the same decision at call time
and routes execution accordingly:

1. every op registers a ``KernelOp`` (``repro.kernels.registry``) with
   its analytic footprint builder and its ``pallas`` / ``xla`` / ``ref``
   backends;
2. a ``DispatchContext`` carries the budget, the packing policy, the
   Pallas ``interpret`` flag, and any backend override (programmatic or
   via the ``REPRO_*`` env knobs in ``repro.flags``);
3. ``dispatch(op, *args, **kwargs)`` builds the op's ``KernelSpec``,
   applies ``core.offload.offload_decision`` (footprint <= budget ->
   ACCEL, else HOST), binds the decision to the preferred available
   backend, runs it, and records the routing in an inspectable trace.

Decisions happen at **trace time** (shapes are static under jit), so a
jitted forward bakes in the routing that was active when it was first
traced — wrap jit entry points in ``use_context`` (see serving/engine).

On CPU the ACCEL decision binds to the plain-XLA binding by default
(Pallas interpreter mode is a correctness tool, not a fast path); set
``allow_pallas=True`` (or ``REPRO_ALLOW_PALLAS=1``) to bind ACCEL to the
Pallas wrappers, as on real TPU.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Mapping, Optional

import jax.numpy as jnp

from repro import flags
from repro.core.footprint import UnsupportedCall
from repro.core.workload import KernelSpec
from repro.kernels.registry import BACKENDS, KernelOp, get_op, register

__all__ = [
    "DispatchContext", "DispatchRecord", "dispatch", "dispatch_counters",
    "dispatch_trace", "grad_safe_context", "reset_dispatch_log",
    "use_context", "current_context",
]


# ----------------------------------------------------------------------------
# Context
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DispatchContext:
    """Everything the control law needs to route one kernel call.

    ``vmem_budget`` is the paper's LMM-size knob: the offload decision
    compares each op's analytic footprint against it, and the Pallas
    wrappers also use it for block selection (C4).
    ``force_backend`` bypasses the control law globally; ``backends``
    does so per-op (``{"q8_matmul": "ref"}``).
    ``platform`` names the registered hardware target this context was
    derived from (``for_platform``); it is stamped into every
    ``DispatchRecord`` so traces are attributable per target. ``tag``
    is a free-form observability label stamped alongside it — e.g. one
    per ServeEngine, so two engines on the same platform can tell their
    trace records apart.
    """

    vmem_budget: int
    policy: str = "optimized"
    interpret: bool = dataclasses.field(
        default_factory=flags.interpret_default)
    allow_pallas: bool = False
    force_backend: Optional[str] = None
    backends: Mapping[str, str] = dataclasses.field(default_factory=dict)
    platform: Optional[str] = None
    tag: Optional[str] = None

    @classmethod
    def for_platform(cls, platform, **overrides) -> "DispatchContext":
        """Derive a context from a registered ``repro.platforms`` target
        (by name or ``Platform`` object): the LMM/VMEM budget, the
        packing policy, and pallas-eligibility all come from the
        platform. The platform says whether its accel path *may* bind to
        Pallas; the environment says whether this process *can* run it
        (``flags.allow_pallas_default()`` — real TPU, or an explicit
        ``REPRO_ALLOW_PALLAS=1``). Keyword ``overrides`` win over both.
        """
        from repro.platforms import get_platform
        p = get_platform(platform)
        kw = dict(
            vmem_budget=p.vmem_budget,
            policy=p.policy,
            interpret=flags.interpret_default(),
            allow_pallas=p.allow_pallas and flags.allow_pallas_default(),
            force_backend=flags.kernel_backend_override(),
            platform=p.name,
        )
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def from_env(cls) -> "DispatchContext":
        name = flags.platform_default()
        if name:
            over = {}
            budget = flags.vmem_budget_override()
            if budget is not None:
                over["vmem_budget"] = budget
            if flags._env_bool("REPRO_ALLOW_PALLAS") is not None:
                over["allow_pallas"] = flags.allow_pallas_default()
            return cls.for_platform(name, **over)
        return cls(
            vmem_budget=flags.vmem_budget_default(),
            interpret=flags.interpret_default(),
            allow_pallas=flags.allow_pallas_default(),
            force_backend=flags.kernel_backend_override(),
        )


_CTX: Optional[DispatchContext] = None


def current_context() -> DispatchContext:
    """The active context: the innermost ``use_context``, else env/defaults."""
    return _CTX if _CTX is not None else DispatchContext.from_env()


def grad_safe_context(ctx: Optional[DispatchContext] = None
                      ) -> DispatchContext:
    """A variant of ``ctx`` that never binds to Pallas. The Pallas
    kernels define no VJP yet, so differentiated forwards (training)
    must stay on the XLA/ref bindings whatever the platform or env
    routing says."""
    ctx = ctx or current_context()
    force = None if ctx.force_backend == "pallas" else ctx.force_backend
    backends = {k: v for k, v in ctx.backends.items() if v != "pallas"}
    return dataclasses.replace(ctx, allow_pallas=False,
                               force_backend=force, backends=backends)


@contextlib.contextmanager
def use_context(ctx: Optional[DispatchContext]):
    """Install ``ctx`` as the dispatch context for the enclosed block.
    ``None`` is a no-op (convenient for optional plumbing)."""
    global _CTX
    if ctx is None:
        yield
        return
    prev = _CTX
    _CTX = ctx
    try:
        yield ctx
    finally:
        _CTX = prev


# ----------------------------------------------------------------------------
# Trace / counters
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DispatchRecord:
    op: str
    decision: str        # "accel" | "host" | "forced" | "accel->host"
    backend: str         # "pallas" | "xla" | "ref"
    footprint: int
    budget: int
    spec: KernelSpec
    platform: str = ""   # registered platform the context was derived from
    tag: str = ""        # caller-scoped label (e.g. one per ServeEngine)


_TRACE_MAX = 1024
_trace: collections.deque = collections.deque(maxlen=_TRACE_MAX)
_counters: collections.Counter = collections.Counter()


def dispatch_trace() -> list[DispatchRecord]:
    return list(_trace)


def dispatch_counters() -> collections.Counter:
    """Counter keyed ``(op, decision, backend)`` — trace-time events."""
    return collections.Counter(_counters)


def reset_dispatch_log() -> None:
    _trace.clear()
    _counters.clear()


# ----------------------------------------------------------------------------
# Dispatch
# ----------------------------------------------------------------------------

def _first_allowed(op: KernelOp, order, ctx: DispatchContext) -> str:
    for b in order:
        if b not in op.backends:
            continue
        if b == "pallas" and not ctx.allow_pallas:
            continue
        return b
    # nothing allowed in the preferred order: take anything registered,
    # honoring the order but ignoring allow_pallas (an op may be
    # pallas-only; correctness beats the platform preference).
    for b in order:
        if b in op.backends:
            return b
    return next(iter(op.backends))


def _decide(op: KernelOp, spec: KernelSpec,
            ctx: DispatchContext) -> tuple[str, str, int]:
    """(decision, backend, footprint) — one footprint evaluation."""
    from repro.core.footprint import kernel_footprint
    footprint = kernel_footprint(spec, ctx.policy)
    forced = ctx.force_backend or ctx.backends.get(op.name)
    if forced:
        if forced not in BACKENDS:
            raise ValueError(
                f"forced backend {forced!r} for {op.name}: expected one "
                f"of {BACKENDS}")
        if forced in op.backends:
            return "forced", forced, footprint
        # a valid backend the op never registered (e.g. a global
        # REPRO_KERNEL_BACKEND=xla hitting a pallas/ref-only op):
        # land it on the op's host chain rather than crashing.
        return "forced", _first_allowed(op, op.host_order, ctx), footprint
    decision = "accel" if footprint <= ctx.vmem_budget else "host"
    order = op.accel_order if decision == "accel" else op.host_order
    return decision, _first_allowed(op, order, ctx), footprint


def decide(op_name: str, spec: KernelSpec,
           ctx: Optional[DispatchContext] = None) -> tuple[str, str]:
    """(decision, backend) the control law would take for ``spec`` —
    the pure half of ``dispatch``, used by the plan-agreement benchmark."""
    decision, backend, _ = _decide(get_op(op_name), spec,
                                   ctx or current_context())
    return decision, backend


def dispatch(op_name: str, *args, ctx: Optional[DispatchContext] = None,
             tag: Optional[str] = None, **kwargs):
    """Route one kernel call through the registered backend the control
    law selects. Returns whatever the backend returns.

    ``tag`` (reserved — never forwarded to the backend) overrides the
    ``KernelSpec.tag`` the op's spec builder stamps, so call sites
    outside the transformer proper (e.g. the audio frontend's mel/
    projection GEMMs, tagged ``"frontend"``) stay distinguishable in the
    dispatch trace and the workload accounting."""
    op = get_op(op_name)
    ctx = ctx or current_context()
    spec = op.spec(*args, **kwargs)
    if tag is not None:
        spec = dataclasses.replace(spec, tag=tag)
    decision, backend, footprint = _decide(op, spec, ctx)
    try:
        out = op.backends[backend](ctx, *args, **kwargs)
    except UnsupportedCall:
        if backend != "pallas" or decision == "forced":
            raise
        # the budget admitted the analytic footprint but the kernel
        # refuses the call on purpose (no MXU-aligned block fits, or a
        # shape class it does not take): land it on the host path, as
        # the paper's residual machinery does. Any other error — a
        # Mosaic lowering failure included — propagates.
        backend = _first_allowed(op, op.host_order, ctx)
        out = op.backends[backend](ctx, *args, **kwargs)
        decision = "accel->host"
    _trace.append(DispatchRecord(op_name, decision, backend, footprint,
                                 ctx.vmem_budget, spec,
                                 platform=ctx.platform or "",
                                 tag=ctx.tag or ""))
    _counters[(op_name, decision, backend)] += 1
    return out


# ----------------------------------------------------------------------------
# Built-in op registrations
# ----------------------------------------------------------------------------

def _flat_m(x) -> int:
    m = 1
    for d in x.shape[:-1]:
        m *= d
    return m


def _register_builtin_ops() -> None:
    from repro.kernels.fp16_matmul.ops import fp16_matmul
    from repro.kernels.fp16_matmul.ref import fp16_matmul_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.paged_attention.ref import paged_decode_attention_ref
    from repro.kernels.paged_attention.xla import paged_decode_attention_xla
    from repro.kernels.q4_attention.ops import q4_decode_attention
    from repro.kernels.q4_attention.ref import q4_decode_attention_ref
    from repro.kernels.q4_attention.xla import q4_decode_attention_xla
    from repro.kernels.q4_matmul.ops import q4_matmul, q4_matmul_xla
    from repro.kernels.q4_matmul.ref import q4_matmul_ref
    from repro.kernels.q8_attention.ops import q8_decode_attention
    from repro.kernels.q8_attention.ref import q8_decode_attention_ref
    from repro.kernels.q8_attention.xla import q8_decode_attention_xla
    from repro.kernels.q8_matmul.ops import q8_matmul, q8_matmul_xla
    from repro.kernels.q8_matmul.ref import q8_matmul_ref
    from repro.kernels.slstm_scan.ops import slstm_scan
    from repro.kernels.slstm_scan.ref import slstm_scan_ref

    # ---- q8_matmul: y = x @ dequant(w), w a (K, N) Q8Tensor ----
    register(KernelOp(
        name="q8_matmul",
        doc="Q8_0 GEMM (weights quantized along K).",
        spec=lambda x, w, **kw: KernelSpec(
            "q8_matmul", m=_flat_m(x), n=w.q.shape[-1], k=x.shape[-1],
            dtype="q8_0", tag="proj"),
        backends={
            "pallas": lambda ctx, x, w, out_dtype=jnp.float32: q8_matmul(
                x, w, vmem_budget=ctx.vmem_budget, out_dtype=out_dtype,
                interpret=ctx.interpret),
            "xla": lambda ctx, x, w, out_dtype=jnp.float32: q8_matmul_xla(
                x, w, out_dtype=out_dtype),
            "ref": lambda ctx, x, w, out_dtype=jnp.float32: q8_matmul_ref(
                x, w.q, w.scale, out_dtype=out_dtype),
        },
    ))

    # ---- q4_matmul: y = x @ dequant(w), w a packed-K Q4Tensor ----
    # One tier below q8_matmul: spec.k is the *logical* K (2x the packed
    # plane rows) so the SC-FOOT bytes band prices the 0.5625 B/elem
    # stream against the same m/n/k as the q8 op.
    register(KernelOp(
        name="q4_matmul",
        doc="Q4_0 GEMM (nibble-packed weights quantized along K).",
        spec=lambda x, w, **kw: KernelSpec(
            "q4_matmul", m=_flat_m(x), n=w.q.shape[-1], k=x.shape[-1],
            dtype="q4_0", tag="proj"),
        backends={
            "pallas": lambda ctx, x, w, out_dtype=jnp.float32: q4_matmul(
                x, w, vmem_budget=ctx.vmem_budget, out_dtype=out_dtype,
                interpret=ctx.interpret),
            "xla": lambda ctx, x, w, out_dtype=jnp.float32: q4_matmul_xla(
                x, w, out_dtype=out_dtype),
            "ref": lambda ctx, x, w, out_dtype=jnp.float32: q4_matmul_ref(
                x, w.q, w.scale, out_dtype=out_dtype),
        },
    ))

    # ---- fp16_matmul: y = x @ w, dense fp16/bf16 operands ----
    # The "xla" binding reproduces models.layers.mm's historical einsum
    # exactly (operands stay in compute dtype; no forced f32 upcast) so
    # host-routed model forwards are bit-identical to the pre-API stack.
    register(KernelOp(
        name="fp16_matmul",
        doc="Dense fp16/bf16 GEMM.",
        spec=lambda x, w, **kw: KernelSpec(
            "fp16_matmul", m=_flat_m(x), n=w.shape[-1], k=x.shape[-1],
            dtype="f16", tag="proj"),
        backends={
            "pallas": lambda ctx, x, w, out_dtype=None: fp16_matmul(
                x, w, vmem_budget=ctx.vmem_budget,
                out_dtype=out_dtype or jnp.float32,
                interpret=ctx.interpret),
            "xla": lambda ctx, x, w, out_dtype=None: (
                jnp.einsum("...k,kn->...n", x, w).astype(out_dtype)
                if out_dtype is not None
                else jnp.einsum("...k,kn->...n", x, w)),
            "ref": lambda ctx, x, w, out_dtype=None: fp16_matmul_ref(
                x, w, out_dtype=out_dtype or jnp.float32),
        },
    ))

    # ---- flash_attention: (B,S,H,D) GQA attention ----
    def _flash_pallas(ctx, q, k, v, *, causal=True, window=None,
                      softcap=None):
        if q.shape[1] != k.shape[1]:
            # the Pallas kernel assumes square S; cross-attention
            # (sq != skv) lands on the host chunked path via dispatch's
            # accel->host fallback.
            raise UnsupportedCall(
                f"flash_attention pallas kernel requires sq == skv, got "
                f"{q.shape[1]} vs {k.shape[1]}")
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, interpret=ctx.interpret)

    def _flash_xla(ctx, q, k, v, *, causal=True, window=None, softcap=None):
        # deferred import: models.attention itself dispatches through
        # this module (call-time import breaks the cycle).
        from repro.models.attention import _repeat_kv, chunked_attention
        h = q.shape[2]
        return chunked_attention(q, _repeat_kv(k, h), _repeat_kv(v, h),
                                 causal=causal, window=window,
                                 softcap=softcap)

    def _flash_ref(ctx, q, k, v, *, causal=True, window=None, softcap=None):
        from repro.models.attention import _repeat_kv
        b, s, h, d = q.shape
        k = _repeat_kv(k, h)
        v = _repeat_kv(v, h)
        sk = k.shape[1]
        out = attention_ref(
            q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
            k.transpose(0, 2, 1, 3).reshape(b * h, sk, d),
            v.transpose(0, 2, 1, 3).reshape(b * h, sk, d),
            causal=causal, window=window, softcap=softcap)
        return out.reshape(b, h, s, d).transpose(0, 2, 1, 3).astype(q.dtype)

    register(KernelOp(
        name="flash_attention",
        doc="GQA flash attention over (B,S,H,D).",
        # count = 2 * B * H: QK^T and AV (equal 2*m*n*k flops) over every
        # batch*query-head plane — one KernelSpec per dispatched call.
        spec=lambda q, k, v, **kw: KernelSpec(
            "flash_attention", m=q.shape[1], n=k.shape[1], k=q.shape[-1],
            dtype="f16", count=2 * q.shape[0] * q.shape[2],
            tag="attn_qk"),
        backends={
            "pallas": _flash_pallas,
            "xla": _flash_xla,
            "ref": _flash_ref,
        },
    ))

    # ---- q8_decode_attention: decode matvec over the Q8_0 KV cache ----
    # count = 2 * BH: the QK^T and AV contractions (same 2*m*n*k flops
    # each) across every batch*head lane in the flattened plane.
    # The "xla" host backend dequantizes into bf16 (never f32 planes) —
    # the ref oracle's full-plane f32 dequant is for parity tests only.
    register(KernelOp(
        name="q8_decode_attention",
        doc="Decode attention reading the Q8_0-quantized KV cache.",
        spec=lambda q, kq, ks, vq, vs, length, **kw: KernelSpec(
            "q8_decode_attention", m=q.shape[1], n=kq.shape[1],
            k=q.shape[-1], dtype="q8_0", count=2 * q.shape[0],
            tag="attn_qk"),
        backends={
            "pallas": lambda ctx, q, kq, ks, vq, vs, length, bk=128:
                q8_decode_attention(q, kq, ks, vq, vs, length, bk=bk,
                                    interpret=ctx.interpret),
            "xla": lambda ctx, q, kq, ks, vq, vs, length, bk=128:
                q8_decode_attention_xla(q, kq, ks, vq, vs, length),
            "ref": lambda ctx, q, kq, ks, vq, vs, length, bk=128:
                q8_decode_attention_ref(q, kq, ks, vq, vs, length),
        },
    ))

    # ---- q4_decode_attention: decode matvec over the Q4_0 KV cache ----
    # Same shape/count conventions as the q8 op; the Pallas binding is
    # single-query (speculative multi-query verify raises UnsupportedCall and
    # lands on the bf16-widened xla backend via accel->host fallback).
    register(KernelOp(
        name="q4_decode_attention",
        doc="Decode attention reading the Q4_0 nibble-packed KV cache.",
        spec=lambda q, kp, ks, vp, vs, length, **kw: KernelSpec(
            "q4_decode_attention", m=q.shape[1], n=kp.shape[1],
            k=q.shape[-1], dtype="q4_0", count=2 * q.shape[0],
            tag="attn_qk"),
        backends={
            "pallas": lambda ctx, q, kp, ks, vp, vs, length, bk=128:
                q4_decode_attention(q, kp, ks, vp, vs, length, bk=bk,
                                    interpret=ctx.interpret),
            "xla": lambda ctx, q, kp, ks, vp, vs, length, bk=128:
                q4_decode_attention_xla(q, kp, ks, vp, vs, length),
            "ref": lambda ctx, q, kp, ks, vp, vs, length, bk=128:
                q4_decode_attention_ref(q, kp, ks, vp, vs, length),
        },
    ))

    # ---- paged_decode_attention: decode matvec over a paged KV pool ----
    # Planes live in a shared (n_pages, P, Hkv, ·) pool; ``table``
    # (B, n_lp) reassembles each lane's logical sequence by gather, so
    # n = n_lp * P plays the role the slot pool's max_len/enc_len played.
    # ``kc``/``vc`` are arrays (bf16 cache) or {"q", "s"} dicts (q8_0).
    # count = 2 * B * H as in the slot-pool decode ops; the page-table
    # gather roughly doubles the K/V byte stream (pool read + gathered
    # copy), which stays inside the SC-FOOT bytes band.
    register(KernelOp(
        name="paged_decode_attention",
        doc="Decode attention gathered over per-lane page tables.",
        spec=lambda q, kc, vc, table, lens, **kw: KernelSpec(
            "paged_decode_attention", m=q.shape[1],
            n=table.shape[1] * (kc["p" if "p" in kc else "q"]
                                if isinstance(kc, dict) else kc).shape[1],
            k=q.shape[-1],
            dtype=(("q4_0" if "p" in kc else "q8_0")
                   if isinstance(kc, dict) else "bf16"),
            count=2 * q.shape[0] * q.shape[2], tag="attn_qk"),
        backends={
            "xla": lambda ctx, q, kc, vc, table, lens:
                paged_decode_attention_xla(q, kc, vc, table, lens),
            "ref": lambda ctx, q, kc, vc, table, lens:
                paged_decode_attention_ref(q, kc, vc, table, lens),
        },
    ))

    # ---- slstm_scan: time-chunked sLSTM recurrence ----
    register(KernelOp(
        name="slstm_scan",
        doc="Chunked sLSTM scan, state resident in VMEM.",
        # count = 4 * T: four gate recurrence matmuls (B*H, hd) @ (hd, hd)
        # per scanned time step.
        spec=lambda wx, r_all, state0, **kw: KernelSpec(
            "slstm_scan", m=wx.shape[2] * wx.shape[3], n=wx.shape[-1],
            k=wx.shape[-1], dtype="f32", count=4 * wx.shape[0],
            tag="ssm"),
        backends={
            "pallas": lambda ctx, wx, r_all, state0, t_chunk=64:
                slstm_scan(wx, r_all, state0, t_chunk=t_chunk,
                           interpret=ctx.interpret),
            "ref": lambda ctx, wx, r_all, state0, t_chunk=64:
                slstm_scan_ref(wx, r_all, state0),
        },
    ))


_register_builtin_ops()
