"""Serving engine: spec-declared per-lane state + jitted prefill/decode.

Per-lane state is **model-declared** (``Model.state_spec()`` →
``LaneStateSpec``), not assumed: attention families carry slot/paged KV
planes, SSM/mLSTM/sLSTM families carry constant-size recurrent buffers
(``conv``/``h``, ``(C, n, m)``, ``(c, n, h, m)``) that are fully
rewritten every step, MoE families add per-lane expert-routing counters
— and one engine serves all of them. Admission (exact-length prefill
for recurrent lanes), the fused decode tick, donation, q8_0 storage,
abort/free, and the traffic/energy accounting all key off the spec;
``LaneStatePool`` (lanestate.py) is the host-side ledger of which state
each live lane holds.

Continuous-batching design (vLLM-style, adapted to JAX's static shapes):

* the engine owns a fixed pool of ``n_slots`` cache slots — one batched
  KV/state cache pytree; every decode tick runs **one** jitted step over
  the whole pool with *per-lane positions* (the model's decode path
  accepts ``pos`` as a (B,) vector), so requests at different depths
  batch together;
* prefill runs per-request at a bucketed sequence length (powers of two:
  compile once per bucket) and the resulting cache is scattered into a
  free lane **inside the prefill jit** (the pool buffer is donated, so
  the scatter is an in-place lane write, and only the first-token argmax
  — a single scalar — crosses back to host, never the
  ``[1, bucket, vocab]`` logits); lanes whose spec sets
  ``prefill_exact`` (recurrent state — scans fold padding into the
  state) prefill at the exact prompt length instead;
* Q8_0 weights (``core.quantize.quantize_tree``) serve through the same
  forward — the paper's quantized serving variant is a flag, not a fork.

Device-resident fused decode (``decode_block``): all per-lane decode
state — last token, position, encoder length, active/EOS masks, emitted
counts, per-lane ``max_new`` budgets — lives in device arrays owned by
the engine. One ``step()`` runs ``decode_block`` decode steps fused in a
single jit (``lax.scan`` over the step body) with the cache pool and
state buffers donated, and syncs to host **once per tick**: the
``(K, n_slots)`` token block plus its emit mask. On-device
EOS/max-new/max-len masking freezes finished lanes mid-scan (their
token/position stop advancing and their emits are masked off), so a
``K``-step fused tick is token-identical to ``K`` single steps. Host
Python then replays the emit mask to run the bookkeeping no jit can:
appending to ``RequestState.out``, freeing slots, pausing streams.

Sync-point inventory (everything that crosses host<->device):
  * ``admit()``/``_anchor()`` — one int32 scalar (the first token);
  * ``step()``       — one fetch of the ``(K, n_slots)`` token block +
    emit mask (``_host_syncs`` counts these; ``_decode_steps`` counts
    the fused decode steps they bought);
  * everything else (lane-state updates at admit/free, stream cross-K/V
    extension) is host->device only and never blocks.

Cache-dtype policy (``cache_dtype="bf16" | "q8_0"``): a q8_0 pool stores
int8+f16-scale planes (``models.attention.init_kv_cache``); prefill
caches are quantized before the slot scatter, decode writes quantize the
new token in place, and the decode cache matvec routes through
``dispatch("q8_decode_attention", ...)`` — the paper's Q8_0 LOAD saving
(~0.53x cache bytes/step, ``kernels.q8_attention.ops.cache_traffic_ratio``)
applied to the decode bottleneck. Recurrent state stays at the spec's
``recurrent_dtype`` (bf16) in both tiers — it is O(1)-sized and fully
rewritten every step, so there is no LOAD win to quantize for; models
with no KV planes at all (pure xLSTM/SSM) reject q8_0 outright.

Encoder-decoder serving (whisper): requests carry ``enc_frames``; admit
encodes them at their exact length (bidirectional attention — padding
would corrupt the states), caches the per-slot encoder K/V in the pool's
cross-cache (padded to ``enc_len``), and decode masks each lane's cross
attention to its true encoder length.

The batch scheduler (scheduler.py) decides admission; this module is the
mechanism: slot allocation, cache scatter, masked fused decode.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import hashlib
import itertools
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import flags, tracing
from repro.core.quantize import (Q4Tensor, Q8Tensor, quantize_q4_0,
                                 quantize_q8_0, quantize_tree,
                                 stored_bytes)
from repro.kernels.api import (DispatchContext, dispatch_counters,
                               dispatch_trace, use_context)
from repro.kernels.q4_attention.ops import cache_traffic_ratio_q4
from repro.kernels.q8_attention.ops import cache_traffic_ratio
from repro.models import encdec as encdec_mod
from repro.models.attention import quantize_kv_cache
from repro.models.model import Model
from repro.paging import PageAllocError, PagedKV
from repro.serving.lanestate import LaneStatePool
from repro.platforms import Platform, get_platform


@contextlib.contextmanager
def _quiet_donation():
    """CPU has no donation support; jit warns once per compile that the
    donated pool/state buffers fell back to copies. The donation is
    still correct (and is what makes TPU/GPU decode update the pool in
    place), so silence exactly that warning — scoped to the engine's
    own jit calls, never process-wide."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


EOS_DEFAULT = 2

CACHE_DTYPES = ("bf16", "q8_0", "q4_0")

QUANT_TIERS = ("q8_0", "q4_0")

_ENGINE_SEQ = itertools.count()   # unique dispatch-trace tags per engine


class RejectCode(enum.Enum):
    """Machine-readable rejection/shed reasons. The first group is
    produced by ``ServeEngine.validate`` (the request can never be
    served by this engine); the second by the gateway's admission and
    lifecycle paths (``repro.gateway`` — load shedding, deadlines,
    client-side aborts). One enum so every failed request, wherever it
    failed, classifies the same way in metrics and tests."""

    # --- engine validation
    TOO_LONG = "too_long"                        # prompt+max_new vs max_len
    MISSING_ENC_INPUT = "missing_enc_input"      # enc-dec model, no frames
    AMBIGUOUS_ENC_INPUT = "ambiguous_enc_input"  # frames AND states given
    BAD_ENC_SHAPE = "bad_enc_shape"              # misshapen frames/chunk
    ENC_OVERFLOW = "enc_overflow"                # frames exceed pool enc_len
    ENC_ON_DECODER_ONLY = "enc_on_decoder_only"  # frames for a text model
    POOL_EXHAUSTED = "pool_exhausted"            # paged KV pool out of pages
    #   (validate: the request's page demand exceeds the whole pool —
    #    permanent; gateway: load-shed because free pages ran low)
    # --- gateway admission / lifecycle (repro.gateway)
    QUEUE_FULL = "queue_full"                    # bounded-queue backpressure
    DEADLINE_UNMEETABLE = "deadline_unmeetable"  # shed at submit (estimate)
    DEADLINE_MISSED = "deadline_missed"          # shed at admit, pre-prefill
    CANCELLED = "cancelled"                      # client cancelled mid-flight
    TIMEOUT = "timeout"                          # client-side timeout_s hit


@dataclasses.dataclass(frozen=True)
class Rejection:
    """A structured rejection: ``code`` for machines, ``message`` for
    humans. ``str(rejection)`` is the human message, so callers that
    only ever stored the string keep working."""

    code: RejectCode
    message: str

    def __str__(self) -> str:
        return self.message


class RejectionError(ValueError):
    """``admit``/``open_stream``/``stream_feed`` failure carrying the
    structured ``Rejection`` (``.rejection``); still a ValueError for
    existing callers."""

    def __init__(self, rejection: Rejection):
        super().__init__(rejection.message)
        self.rejection = rejection


@dataclasses.dataclass
class Request:
    uid: int
    tokens: list             # prompt token ids
    max_new: int = 16
    eos_id: int = EOS_DEFAULT
    # enc-dec (audio) requests: precomputed frame embeddings
    # (S_enc, d_model); required when the served model is enc_dec.
    enc_frames: Optional[Any] = None
    # alternatively, precomputed *encoder states* (S_enc, d_model) —
    # e.g. from the chunked streaming encoder — which skip the
    # engine-side encode entirely (exactly one of the two for enc-dec).
    enc_states: Optional[Any] = None


@dataclasses.dataclass
class AudioRequest(Request):
    """A Request that must carry encoder input — the whisper serving
    path: either ``enc_frames`` (encoded once at admit) or precomputed
    ``enc_states`` (chunked/streaming encode output). Same scheduler/
    engine treatment as text requests; the encoder result is cached per
    slot."""

    def __post_init__(self):
        if self.enc_frames is None and self.enc_states is None:
            raise ValueError(
                f"AudioRequest {self.uid} requires enc_frames or "
                f"enc_states")


@dataclasses.dataclass
class StreamingAudioRequest(Request):
    """An audio request whose encoder frames arrive incrementally.

    ``chunks`` is the list of frame-embedding chunks ((s_i, d_model),
    fixed size except the tail — ``repro.audio.stream`` produces them
    from raw samples). The scheduler feeds one chunk per tick through
    ``ServeEngine.open_stream``/``stream_feed``: each chunk is encoded
    once (block-diagonal chunked encode), the slot's cached encoder K/V
    is *extended* in place, and the lane's ``enc_lens`` grows — decode
    ticks in between emit partial hypotheses (``RequestState.partials``).
    ``stream_finalize`` re-anchors the prompt against the full audio, so
    the final transcript is token-identical to one-shot serving."""

    chunks: Optional[list] = None

    def __post_init__(self):
        if not self.chunks:
            raise ValueError(
                f"StreamingAudioRequest {self.uid} requires a non-empty "
                f"list of frame chunks")
        if self.enc_frames is not None or self.enc_states is not None:
            raise ValueError(
                f"StreamingAudioRequest {self.uid}: frames arrive via "
                f"chunks, not enc_frames/enc_states")


@dataclasses.dataclass
class RequestState:
    req: Request
    slot: int
    pos: int                 # next position to write
    out: list                # generated ids
    done: bool = False
    error: Optional[str] = None   # set when rejected/failed, slot == -1
    error_code: Optional[RejectCode] = None   # machine-readable reason
    # streaming requests: one snapshot of ``out`` per fed audio chunk
    # (the partial hypotheses emitted while audio was still arriving)
    partials: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class PendingTick:
    """A dispatched-but-unfetched fused decode tick (``step_begin``):
    the device arrays holding the ``(k, n_slots)`` token block and emit
    mask, still materializing on device until ``step_fetch`` blocks on
    them."""

    k: int
    tok_blk: Any
    emit_blk: Any


@dataclasses.dataclass
class _StreamState:
    """Engine-side state of one open audio stream (slot-keyed)."""
    states: list                  # encoded chunk states, each (1, s_i, d)
    n_frames: int = 0             # frames fed == valid encoder positions
    anchored: bool = False        # prompt prefill has run at least once


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


class ServeEngine:
    def __init__(self, model: Model, params: Any, *, n_slots: int = 8,
                 max_len: int = 256, enc_len: int = 64,
                 cache_dtype: str = "bf16",
                 decode_block: int = 1,
                 platform: Optional[Any] = None,
                 dispatch_ctx: Optional[DispatchContext] = None,
                 paged: bool = False, page_size: int = 8,
                 n_pages: Optional[int] = None,
                 n_cross_pages: Optional[int] = None,
                 spec_k: int = 0, draft_dtype: str = "q4_0",
                 draft_params: Optional[Any] = None):
        """``platform``: a registered hardware target (name or
        ``repro.platforms.Platform``). Supplies the default dispatch
        context (``DispatchContext.for_platform``) and enables
        ``energy_report()`` — the paper's joules-per-token accounting on
        the serving path.

        ``dispatch_ctx``: kernel-routing context (budget, backend
        policy — repro.kernels.api) applied while the prefill/decode
        functions trace; None uses the platform-derived (or env/default)
        context. Routing is baked in at first trace, so construct one
        engine per context.

        ``cache_dtype``: "bf16" (dense planes), "q8_0" (int8+scale
        planes, decode reads via the q8_decode_attention op), or
        "q4_0" (nibble-packed uint8+scale planes via
        q4_decode_attention — ~0.28x bf16 cache bytes/step).

        ``spec_k``: > 0 enables self-speculative decoding — each round
        drafts ``spec_k - 1`` tokens with ``draft_dtype``-quantized
        weights and verifies all ``spec_k`` positions in ONE full-model
        forward, inside the same donated tick (still exactly one host
        sync per tick). ``decode_block`` must be a multiple of
        ``spec_k``. Greedy decode only; token-identical to plain
        serving. ``draft_params`` overrides the engine-built draft
        weights (``quantize_tree(params, tier=draft_dtype)``) — pass it
        when the served params are already quantized.

        ``decode_block``: decode steps fused per ``step()`` tick (one
        host sync per tick regardless of the block size). A mutable
        knob — ``engine.decode_block = 16`` retunes a live engine; one
        compile per distinct block size.

        ``paged=True`` (enc-dec only): the per-lane slot pool becomes a
        shared page pool (``repro.paging``) — ``n_pages`` self-KV and
        ``n_cross_pages`` cross-KV pages of ``page_size`` tokens (page 0
        is reserved scratch; defaults size the pools to the slot pool's
        byte budget), with per-lane page tables carried through the
        donated decode jit. Lanes hold ``ceil((n+max_new)/P)`` self and
        ``ceil(enc_s/P)`` cross pages — actual request bytes, not
        ``max_len``/``enc_len`` padding — and identical anchor-prompt /
        audio prefixes share pages copy-on-write. Decode output is
        token-identical to the slot pool (same projections, same masked
        softmax over the gathered pages)."""
        if cache_dtype not in CACHE_DTYPES:
            raise ValueError(f"cache_dtype {cache_dtype!r}: expected one "
                             f"of {CACHE_DTYPES}")
        if int(decode_block) < 1:
            raise ValueError(f"decode_block must be >= 1, got "
                             f"{decode_block}")
        cfg = model.cfg
        # the model-declared per-lane state (LaneStateSpec): which state
        # kinds a lane carries, how prefill must run, and whether the
        # q8_0 tier applies — every family-specific decision below keys
        # off this instead of the config
        self.spec = model.state_spec()
        if cache_dtype in QUANT_TIERS:
            if flags.BASELINE:
                raise ValueError(f"cache_dtype={cache_dtype!r} needs the "
                                 f"stacked decode path (unset "
                                 f"REPRO_BASELINE)")
            if not self.spec.self_kv and not self.spec.cross_kv:
                raise ValueError(
                    f"cache_dtype={cache_dtype!r} quantizes attention KV "
                    f"planes; {cfg.name} lanes carry only recurrent "
                    f"state ({'/'.join(self.spec.recurrent)}) — serve it "
                    f"with cache_dtype='bf16'")
            if cfg.attn_softcap is not None or cfg.sliding_window \
                    is not None or cfg.local_global:
                raise ValueError(
                    f"cache_dtype={cache_dtype!r} supports plain softmax "
                    f"decode attention only; {cfg.name} uses "
                    f"softcap/windowed attention")
            if cfg.head_dim % 32:
                raise ValueError(
                    f"cache_dtype={cache_dtype!r} blocks scales 32-wide "
                    f"along head_dim; {cfg.name} has "
                    f"head_dim={cfg.head_dim}")
            if not self.spec.supports_tier(cache_dtype):
                raise ValueError(
                    f"{cfg.name} declares quant tiers "
                    f"{self.spec.quant_tiers}; cache_dtype="
                    f"{cache_dtype!r} is not among them")
        self.platform: Optional[Platform] = \
            get_platform(platform) if platform is not None else None
        if dispatch_ctx is None and self.platform is not None:
            # the tag scopes this engine's trace records: two engines on
            # the same platform in one process stay distinguishable
            dispatch_ctx = DispatchContext.for_platform(
                self.platform,
                tag=f"serve:{self.platform.name}#{next(_ENGINE_SEQ)}")
        self.model = model
        self.params = params
        self.dispatch_ctx = dispatch_ctx
        self.n_slots = n_slots
        self.max_len = max_len
        self.enc_len = enc_len
        self.enc_dec = bool(cfg.enc_dec)
        self.cache_dtype = cache_dtype
        self.decode_block = int(decode_block)
        cdt = cache_dtype if cache_dtype in QUANT_TIERS else jnp.bfloat16
        # --- self-speculative decoding (draft with quantized weights,
        # verify every position in one full-model multi-query forward)
        self.spec_k = int(spec_k)
        self.draft_dtype = draft_dtype
        self.draft_params = None
        if self.spec_k:
            if self.spec_k < 2:
                raise ValueError(f"spec_k must be >= 2 (1 draft + 1 "
                                 f"verify minimum), got {spec_k}")
            if flags.BASELINE:
                raise ValueError("speculative decoding needs the stacked "
                                 "decode path (unset REPRO_BASELINE)")
            if draft_dtype not in QUANT_TIERS:
                raise ValueError(f"draft_dtype {draft_dtype!r}: expected "
                                 f"one of {QUANT_TIERS}")
            if not self.spec.self_kv:
                raise ValueError(
                    f"speculative decoding rewinds self-KV write "
                    f"cursors; {cfg.name} lanes carry "
                    f"{'/'.join(self.spec.recurrent) or 'no'} recurrent "
                    f"state, which cannot be rolled back")
            if self.spec.moe_experts:
                raise ValueError(
                    f"speculative decoding does not thread the per-lane "
                    f"routing counters through draft/verify; {cfg.name} "
                    f"is MoE")
            if cfg.attn_softcap is not None or cfg.sliding_window \
                    is not None or cfg.local_global:
                raise ValueError(
                    f"speculative decoding supports plain softmax decode "
                    f"attention only; {cfg.name} uses softcap/windowed "
                    f"attention")
            if self.decode_block % self.spec_k:
                raise ValueError(
                    f"decode_block ({decode_block}) must be a multiple "
                    f"of spec_k ({spec_k}): a tick scans "
                    f"decode_block // spec_k draft-verify rounds")
            if draft_params is not None:
                self.draft_params = draft_params
            else:
                # QTensors are pytree nodes: flattening blindly would
                # dissolve them into plain arrays and hide the tier
                leaves = jax.tree.leaves(
                    params,
                    is_leaf=lambda l: isinstance(l, (Q4Tensor, Q8Tensor)))
                if not all(isinstance(l, jax.Array) for l in leaves):
                    raise ValueError(
                        "served params are already quantized; pass "
                        "draft_params= explicitly (the engine builds "
                        "draft weights from float params only)")
                self.draft_params = quantize_tree(params,
                                                  tier=draft_dtype)
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.pages: Optional[PagedKV] = None
        if self.paged:
            if not self.enc_dec:
                raise ValueError(
                    f"paged=True requires an enc-dec model; {cfg.name} "
                    f"is decoder-only")
            if flags.BASELINE:
                raise ValueError("paged=True needs the stacked decode "
                                 "path (unset REPRO_BASELINE)")
            if max_len % self.page_size or enc_len % self.page_size:
                raise ValueError(
                    f"max_len ({max_len}) and enc_len ({enc_len}) must "
                    f"be multiples of page_size ({self.page_size})")
            # defaults match the slot pool's byte budget (+1 scratch)
            if n_pages is None:
                n_pages = n_slots * (max_len // self.page_size) + 1
            if n_cross_pages is None:
                n_cross_pages = n_slots * (enc_len // self.page_size) + 1
            self.pages = PagedKV(
                n_slots=n_slots, max_len=max_len, enc_len=enc_len,
                page_size=self.page_size, n_pages=n_pages,
                n_cross_pages=n_cross_pages)
            self.cache = model.init_paged_cache(
                n_pages, n_cross_pages, self.page_size, dtype=cdt)
        else:
            self.cache = model.init_cache(n_slots, max_len, enc_len,
                                          dtype=cdt)
        self.free = list(range(n_slots))
        self.active: dict[int, RequestState] = {}   # slot -> state
        # host-side ledger of which state each lane holds (reserved at
        # admit/open_stream, extended per streamed chunk, released by
        # _free_slot) — the conformance suite's leak check
        self.lanestate = LaneStatePool(n_slots)
        # --- device-resident decode state (never re-uploaded per tick):
        # last emitted token, write position, valid encoder length, and
        # the per-lane masks/budgets the fused scan needs to freeze
        # finished lanes on device. Parked lanes decode at pos 0 (one
        # attendable position) with active=False so their emits are
        # masked; _free_slot zeroes pos/tokens so a dead lane never
        # attends its stale context.
        self._tokens = jnp.zeros((n_slots, 1), jnp.int32)
        self._pos = jnp.zeros((n_slots,), jnp.int32)
        self._enc_lens = jnp.zeros((n_slots,), jnp.int32)
        self._lane_active = jnp.zeros((n_slots,), bool)
        self._lane_eos = jnp.zeros((n_slots,), jnp.int32)
        self._lane_max = jnp.zeros((n_slots,), jnp.int32)
        self._lane_out = jnp.zeros((n_slots,), jnp.int32)
        # _set_lane's program: the seven vectors above, donated; one
        # compile per engine (slot and values are traced)
        self._write_lane = jax.jit(_write_lane, donate_argnums=(0,))
        self._decode_fns: dict[int, Any] = {}   # block size -> fused jit
        self._prefill_fns: dict[tuple, Any] = {}
        # streaming audio: open streams by slot + jitted encoder helpers
        # (jit retraces per chunk length — fixed chunks + one tail)
        self._streams: dict[int, _StreamState] = {}
        if self.enc_dec:
            cfg_ = cfg
            self._encode = jax.jit(self.model.encode)
            self._cross_kv = jax.jit(
                lambda params, states: encdec_mod.cross_attn_kv(
                    params, cfg_, states))
            self._extend = jax.jit(
                functools.partial(
                    _extend_paged_cross_cache if self.paged
                    else _extend_cross_cache,
                    tier=cache_dtype if cache_dtype in QUANT_TIERS
                    else None),
                donate_argnums=(0,))
        # serving-energy accounting (energy_report)
        self._ticks = 0         # executed fused decode ticks (host syncs)
        self._decode_steps = 0  # executed full-model decode steps
        self._generated = 0     # tokens emitted (prefill firsts + decode)
        self._host_syncs = 0    # device->host fetches on the decode path
        # speculative accounting: draft forwards, multi-query verify
        # forwards, rounds, and the emit stats behind the acceptance rate
        self._draft_steps = 0
        self._verify_steps = 0
        self._spec_rounds = 0
        self._spec_emitted = 0      # tokens emitted by spec ticks
        self._spec_live_rounds = 0  # (round, lane) pairs that emitted

    # ------------------------------------------------------------------
    def _build_decode(self, k: int):
        """The fused decode tick: ``k`` decode steps scanned inside one
        jit. Carry = (cache, tokens, pos, active, n_out) — all donated,
        so the KV pool and lane state are updated in place instead of
        copied every step. Finished lanes (EOS / max_new / max_len) are
        frozen on device: their token/pos stop advancing and their
        emits are masked, which makes the fused tick token-identical to
        ``k`` sequential single steps.

        Paged engines take the per-lane page tables as an extra donated
        argument; the tick never remaps pages, so the tables pass
        through unchanged (aliased outputs) and the engine re-adopts
        them after the donation invalidated the inputs."""
        if self.spec_k:
            return self._build_spec_decode(k)
        model, enc_dec, max_len = self.model, self.enc_dec, self.max_len

        if self.paged:
            @functools.partial(jax.jit,
                               donate_argnums=(1, 2, 3, 4, 5, 6))
            def paged_decode_block(params, cache, tables, tokens, pos,
                                   active, n_out, enc_lens, eos, max_new):
                def one(carry, _):
                    cache, tokens, pos, active, n_out = carry
                    batch = {"tokens": tokens, "enc_lens": enc_lens}
                    logits, cache = model.forward(
                        params, batch, mode="decode", cache=cache,
                        pos=pos, pages=tables)
                    nxt = jnp.argmax(logits[:, -1],
                                     axis=-1).astype(jnp.int32)
                    emit = active
                    tokens = jnp.where(active[:, None], nxt[:, None],
                                       tokens)
                    pos = jnp.where(active, pos + 1, pos)
                    n_out = jnp.where(active, n_out + 1, n_out)
                    stop = (nxt == eos) | (n_out >= max_new) \
                        | (pos >= max_len - 1)
                    active = active & ~stop
                    return (cache, tokens, pos, active, n_out), (nxt, emit)

                carry = (cache, tokens, pos, active, n_out)
                carry, (tok_blk, emit_blk) = jax.lax.scan(
                    one, carry, None, length=k)
                cache, tokens, pos, active, n_out = carry
                return (tok_blk, emit_blk, cache, tables, tokens, pos,
                        active, n_out)

            return paged_decode_block

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5))
        def decode_block(params, cache, tokens, pos, active, n_out,
                         enc_lens, eos, max_new):
            def one(carry, _):
                cache, tokens, pos, active, n_out = carry
                batch = {"tokens": tokens}
                if enc_dec:
                    batch["enc_lens"] = enc_lens
                logits, cache = model.forward(
                    params, batch, mode="decode", cache=cache, pos=pos)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                emit = active
                tokens = jnp.where(active[:, None], nxt[:, None], tokens)
                pos = jnp.where(active, pos + 1, pos)
                n_out = jnp.where(active, n_out + 1, n_out)
                stop = (nxt == eos) | (n_out >= max_new) \
                    | (pos >= max_len - 1)
                active = active & ~stop
                return (cache, tokens, pos, active, n_out), (nxt, emit)

            carry = (cache, tokens, pos, active, n_out)
            carry, (tok_blk, emit_blk) = jax.lax.scan(
                one, carry, None, length=k)
            cache, tokens, pos, active, n_out = carry
            return tok_blk, emit_blk, cache, tokens, pos, active, n_out

        return decode_block

    def _build_spec_decode(self, k: int):
        """The fused *speculative* decode tick: ``k // spec_k``
        draft-verify rounds scanned inside one donated jit.

        Each round, per lane:

        * **draft** — ``spec_k - 1`` greedy steps with the quantized
          draft weights, writing draft KV at ``pos .. pos+spec_k-2``;
        * **verify** — ONE multi-query full-model forward over
          ``[token, d_0, .., d_{spec_k-2}]`` at the same positions
          (its writes overwrite every draft KV entry with
          full-precision-projected values), giving the true greedy
          continuation ``o_j`` at every position;
        * **accept** — the emitted prefix is ``o_0 .. o_{m-1}`` where
          ``m-1`` counts leading draft hits (``d_j == o_j``), cut
          further by the same EOS/max_new/max_len stops the plain tick
          applies. ``pos`` advances by ``m`` — rejected tails are
          rolled back by *not* advancing the write cursor; the next
          round's writes land on top of the garbage before any query
          ever attends it.

        ``o_0`` is exactly the plain tick's argmax, so the emitted
        stream is token-identical to plain greedy decode; a round
        always makes >= 1 token of progress per active lane. Stacked
        rounds yield the same ``(k, n_slots)`` token/emit block
        contract (rows ``r*spec_k .. r*spec_k+m-1`` of round ``r`` are
        emitted; the emit mask is no longer prefix-contiguous across
        rounds, which ``step_replay`` handles). Still exactly one host
        sync per tick."""
        model, enc_dec, max_len = self.model, self.enc_dec, self.max_len
        spec_k = self.spec_k
        gamma = spec_k - 1
        n_rounds = k // spec_k
        draft_params_const = self.draft_params
        paged = self.paged

        def spec_round(params, tables, enc_lens, eos, max_new, carry, _):
            cache, tokens, pos, active, n_out = carry
            kw = {"pages": tables} if paged else {}

            # --- draft: gamma greedy steps with the quantized weights
            def draft_one(c, _):
                dcache, dtok, dpos = c
                batch = {"tokens": dtok}
                if enc_dec:
                    batch["enc_lens"] = enc_lens
                logits, dcache = model.forward(
                    draft_params_const, batch, mode="decode",
                    cache=dcache, pos=dpos, **kw)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (dcache, nxt[:, None], dpos + 1), nxt

            (cache, _, _), drafts = jax.lax.scan(
                draft_one, (cache, tokens, pos), None, length=gamma)
            drafts = drafts.T                      # (B, gamma)

            # --- verify: one multi-query full-model forward over the
            # current token plus every draft, at positions pos..pos+gamma
            ver_in = jnp.concatenate([tokens, drafts], axis=1)
            batch = {"tokens": ver_in}
            if enc_dec:
                batch["enc_lens"] = enc_lens
            logits, cache = model.forward(
                params, batch, mode="decode", cache=cache, pos=pos, **kw)
            o = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, spec_k)

            # --- accept: leading draft hits, cut by the stop conditions
            nb = tokens.shape[0]
            match = drafts == o[:, :gamma]
            prefix_ok = jnp.concatenate(
                [jnp.ones((nb, 1), bool),
                 jnp.cumprod(match, axis=1) > 0], axis=1)
            jj = jnp.arange(spec_k)[None, :]
            cand_stop = (o == eos[:, None]) \
                | (n_out[:, None] + jj + 1 >= max_new[:, None]) \
                | (pos[:, None] + jj + 1 >= max_len - 1)
            no_prior_stop = jnp.concatenate(
                [jnp.ones((nb, 1), bool),
                 jnp.cumprod(~cand_stop[:, :-1], axis=1) > 0], axis=1)
            emit = active[:, None] & prefix_ok & no_prior_stop
            m = emit.sum(axis=1).astype(jnp.int32)
            last = jnp.take_along_axis(
                o, jnp.clip(m - 1, 0, spec_k - 1)[:, None], axis=1)[:, 0]
            tokens = jnp.where(m > 0, last, tokens[:, 0])[:, None]
            pos = pos + m
            n_out = n_out + m
            active = active & ~(emit & cand_stop).any(axis=1)
            return (cache, tokens, pos, active, n_out), (o.T, emit.T)

        if paged:
            @functools.partial(jax.jit,
                               donate_argnums=(1, 2, 3, 4, 5, 6))
            def paged_spec_block(params, cache, tables, tokens, pos,
                                 active, n_out, enc_lens, eos, max_new):
                carry = (cache, tokens, pos, active, n_out)
                carry, (tok_blk, emit_blk) = jax.lax.scan(
                    functools.partial(spec_round, params, tables,
                                      enc_lens, eos, max_new),
                    carry, None, length=n_rounds)
                cache, tokens, pos, active, n_out = carry
                # (n_rounds, spec_k, B) -> the plain (k, B) block shape
                tok_blk = tok_blk.reshape(k, -1)
                emit_blk = emit_blk.reshape(k, -1)
                return (tok_blk, emit_blk, cache, tables, tokens, pos,
                        active, n_out)

            return paged_spec_block

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5))
        def spec_block(params, cache, tokens, pos, active, n_out,
                       enc_lens, eos, max_new):
            carry = (cache, tokens, pos, active, n_out)
            carry, (tok_blk, emit_blk) = jax.lax.scan(
                functools.partial(spec_round, params, None, enc_lens,
                                  eos, max_new),
                carry, None, length=n_rounds)
            cache, tokens, pos, active, n_out = carry
            tok_blk = tok_blk.reshape(k, -1)
            emit_blk = emit_blk.reshape(k, -1)
            return tok_blk, emit_blk, cache, tokens, pos, active, n_out

        return spec_block

    def _decode_fn(self, k: int):
        fn = self._decode_fns.get(k)
        if fn is None:
            fn = self._decode_fns[k] = self._build_decode(k)
        return fn

    def _prefill_fn(self, bucket: int, enc_s: Optional[int] = None,
                    from_states: bool = False):
        """Jitted prefill, keyed (token bucket, encoder length, input
        kind). ``from_states=True`` takes precomputed encoder states
        (streaming chunked encode / ``Request.enc_states``) instead of
        frame embeddings, skipping the in-prefill encoder pass.

        The function takes the whole slot pool (donated: the scatter is
        an in-place lane write) and returns ``(first, pool)`` where
        ``first`` is the argmax of the last prompt position — computed
        on device so admission fetches one scalar, not the full
        ``[1, bucket, vocab]`` logits.

        Paged engines replace the ``slot`` index with two physical-page
        vectors (one per pool): the dense batch-1 cache is reshaped into
        page rows and scattered at the lane's pages — unmapped logical
        pages point at the scratch page, which absorbs the padding."""
        key = (bucket, enc_s, from_states)
        if key not in self._prefill_fns:
            model, max_len, enc_len = self.model, self.max_len, self.enc_len
            tier = self.cache_dtype \
                if self.cache_dtype in QUANT_TIERS else None
            enc_key = "enc_states" if from_states else "enc_frames"
            page_size = self.page_size

            if self.paged:
                @functools.partial(jax.jit, donate_argnums=(1,))
                def paged_prefill(params, pool, tokens, n, pv_self,
                                  pv_cross, enc=None):
                    cache = model.init_cache(1, max_len, enc_len)
                    batch = {"tokens": tokens}
                    if enc is not None:
                        batch[enc_key] = enc
                    logits, cache = model.forward(
                        params, batch, mode="prefill", cache=cache)
                    if tier:
                        cache = quantize_kv_cache(cache, tier)
                    pool = _scatter_pages(pool, cache, pv_self, pv_cross,
                                          page_size)
                    first = jnp.argmax(
                        jnp.take(logits[0], n - 1,
                                 axis=0)).astype(jnp.int32)
                    return first, pool

                self._prefill_fns[key] = paged_prefill
                return paged_prefill

            @functools.partial(jax.jit, donate_argnums=(1,))
            def prefill(params, pool, tokens, n, slot, enc=None):
                cache = model.init_cache(1, max_len, enc_len)
                # n_valid: bucket padding must not win MoE expert
                # capacity (non-enc-dec families ignore it)
                batch = {"tokens": tokens, "n_valid": n}
                if enc is not None:
                    batch[enc_key] = enc
                logits, cache = model.forward(params, batch,
                                              mode="prefill", cache=cache)
                if tier:
                    cache = quantize_kv_cache(cache, tier)
                pool = _scatter_slot(pool, cache, slot)
                first = jnp.argmax(
                    jnp.take(logits[0], n - 1, axis=0)).astype(jnp.int32)
                return first, pool

            self._prefill_fns[key] = prefill
        return self._prefill_fns[key]

    def _set_lane(self, slot: int, *, token: int, pos: int, enc_len: int,
                  eos: int, max_new: int, n_out: int,
                  active: bool) -> None:
        """Write one lane's device-resident decode state: at admission,
        at a stream's anchor, and when a lane is freed (``_free_slot``,
        which ``step_replay`` runs on the tick path for every finished
        lane). One launch of the donated ``_write_lane`` program with
        the values in one small host array."""
        with tracing.span("engine.set_lane") as sp:
            if sp is not None:
                sp.attrs["slot"] = slot
            row = np.array([slot, token, pos, enc_len, eos, max_new, n_out,
                            active], np.int32)
            with _quiet_donation():
                (self._tokens, self._pos, self._enc_lens, self._lane_eos,
                 self._lane_max, self._lane_out, self._lane_active) = \
                    self._write_lane(
                        (self._tokens, self._pos, self._enc_lens,
                         self._lane_eos, self._lane_max, self._lane_out,
                         self._lane_active), row)

    # ------------------------------------------------------------------
    def validate(self, req: Request) -> Optional[Rejection]:
        """Admission precheck: a ``Rejection`` (machine-readable
        ``code`` + human ``message``; the request can never be served by
        this engine), or None. The scheduler rejects failing requests at
        submit() instead of dying mid-tick; the gateway's shed
        accounting classifies by ``code``."""
        C = RejectCode
        n = len(req.tokens)
        # speculative lanes write draft/verify KV up to spec_k - 1
        # positions past the last emitted token before the stop masks
        # bind — keep that whole extent inside the pool so slab writes
        # never clamp onto live positions
        headroom = self.spec_k - 1 if self.spec_k else 0
        if n + req.max_new + headroom >= self.max_len:
            return Rejection(C.TOO_LONG,
                             f"request {req.uid} too long for engine "
                             f"({n}+{req.max_new}"
                             + (f"+{headroom} speculative headroom"
                                if headroom else "")
                             + f" vs {self.max_len})")
        d_model = self.model.cfg.d_model
        if self.enc_dec:
            if isinstance(req, StreamingAudioRequest):
                total = 0
                for i, c in enumerate(req.chunks):
                    shp = np.shape(c)
                    if len(shp) != 2 or shp[1] != d_model or shp[0] < 1:
                        return Rejection(
                            C.BAD_ENC_SHAPE,
                            f"request {req.uid}: chunk {i} must be "
                            f"(s, {d_model}) with s >= 1, got {shp}")
                    total += shp[0]
                if total > self.enc_len:
                    return Rejection(
                        C.ENC_OVERFLOW,
                        f"request {req.uid}: {total} streamed encoder "
                        f"frames exceed the pool enc_len {self.enc_len}")
                if self.paged and not self.pages.fits(
                        n, req.max_new + headroom, total):
                    return Rejection(
                        C.POOL_EXHAUSTED,
                        f"request {req.uid}: page demand exceeds the "
                        f"whole pool (can never be admitted)")
                return None
            if req.enc_frames is None and req.enc_states is None:
                return Rejection(
                    C.MISSING_ENC_INPUT,
                    f"request {req.uid}: enc-dec model "
                    f"{self.model.cfg.name} requires enc_frames or "
                    f"enc_states")
            if req.enc_frames is not None and req.enc_states is not None:
                return Rejection(
                    C.AMBIGUOUS_ENC_INPUT,
                    f"request {req.uid}: pass enc_frames or enc_states, "
                    f"not both")
            enc = req.enc_frames if req.enc_frames is not None \
                else req.enc_states
            what = "enc_frames" if req.enc_frames is not None \
                else "enc_states"
            shp = np.shape(enc)
            if len(shp) != 2 or shp[1] != d_model:
                return Rejection(C.BAD_ENC_SHAPE,
                                 f"request {req.uid}: {what} must be "
                                 f"(S_enc, {d_model}), got {shp}")
            if shp[0] > self.enc_len:
                return Rejection(
                    C.ENC_OVERFLOW,
                    f"request {req.uid}: {shp[0]} encoder positions "
                    f"exceed the pool enc_len {self.enc_len}")
            if self.paged and not self.pages.fits(
                    n, req.max_new + headroom, shp[0]):
                return Rejection(
                    C.POOL_EXHAUSTED,
                    f"request {req.uid}: page demand exceeds the whole "
                    f"pool (can never be admitted)")
        elif req.enc_frames is not None or req.enc_states is not None \
                or isinstance(req, StreamingAudioRequest):
            return Rejection(
                C.ENC_ON_DECODER_ONLY,
                f"request {req.uid}: encoder input on decoder-only "
                f"model {self.model.cfg.name}")
        return None

    def admit(self, req: Request) -> Optional[RequestState]:
        """Prefill a request into a free slot; None if the pool is full.
        Raises ValueError for requests that can never be served (use
        ``validate`` to precheck)."""
        if isinstance(req, StreamingAudioRequest):
            raise ValueError(
                f"request {req.uid}: streaming requests are served via "
                f"open_stream/stream_feed (or BatchScheduler.submit)")
        if not self.free:
            return None
        with tracing.span("engine.admit") as sp:
            # host preparation: prompt, frames to the device, pages
            with tracing.span("engine.admit.inputs"):
                err = self.validate(req)
                if err is not None:
                    raise RejectionError(err)
                n = len(req.tokens)
                slot = self.free.pop()
                # recurrent lanes (LaneStateSpec.prefill_exact) fold every
                # input position into the end-of-prompt state, so bucket
                # zero-padding would corrupt it — prefill at the exact
                # prompt length (one compile per distinct length;
                # attention-only lanes keep the power-of-2 bucket grid)
                bucket = n if self.spec.prefill_exact \
                    else min(_bucket(n), self.max_len)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :n] = req.tokens
                enc_s = None
                # resolve the encoder input host-side first: the paged path
                # needs enc_s (and the content digest) before any page moves
                states = frames = None
                if self.enc_dec and req.enc_states is not None:
                    # precomputed encoder states (chunked/streaming encode):
                    # prefill skips the encoder pass entirely.
                    states = jnp.asarray(req.enc_states)[None]
                    enc_s = int(states.shape[1])
                elif self.enc_dec:
                    # encode at the exact frame count: the encoder attends
                    # bidirectionally, so bucket padding would corrupt every
                    # frame state (one compile per distinct enc_s).
                    frames = jnp.asarray(np.asarray(req.enc_frames),
                                         jnp.float32)[None]
                    enc_s = int(frames.shape[1])
                pv_self = pv_cross = None
                if self.paged:
                    from_states = req.enc_states is not None
                    digest = _enc_digest(
                        req.enc_states if from_states else req.enc_frames,
                        "states" if from_states else "frames")
                    try:
                        self.pages.admit_lane(
                            slot, req.tokens, digest,
                            max_new=req.max_new + (self.spec_k - 1
                                                   if self.spec_k else 0),
                            enc_s=enc_s)
                    except PageAllocError:
                        # transient: pages drain as lanes finish — same retry
                        # contract as a full slot pool (scheduler re-queues)
                        self.free.append(slot)
                        return None
                    pv_self = jnp.asarray(self.pages.self_table.row(slot),
                                          jnp.int32)
                    pv_cross = jnp.asarray(self.pages.cross_table.row(slot),
                                           jnp.int32)
            if sp is not None:
                sp.attrs.update(uid=req.uid, bucket=bucket, enc_s=enc_s)
            with tracing.span("engine.prefill"), \
                    use_context(self.dispatch_ctx), _quiet_donation():
                if self.paged:
                    fn = self._prefill_fn(bucket, enc_s,
                                          from_states=states is not None)
                    first, self.cache = fn(
                        self.params, self.cache, jnp.asarray(toks), n,
                        pv_self, pv_cross,
                        states if states is not None else frames)
                elif states is not None:
                    first, self.cache = self._prefill_fn(
                        bucket, enc_s, from_states=True)(
                            self.params, self.cache, jnp.asarray(toks), n,
                            slot, states)
                elif self.enc_dec:
                    first, self.cache = self._prefill_fn(bucket, enc_s)(
                        self.params, self.cache, jnp.asarray(toks), n, slot,
                        frames)
                else:
                    first, self.cache = self._prefill_fn(bucket)(
                        self.params, self.cache, jnp.asarray(toks), n, slot)
            with tracing.span("engine.first_token"):
                first = int(first)   # scalar fetch: the only admit sync
            self._generated += 1
            self.lanestate.reserve(slot, self.spec, n_tokens=n + req.max_new,
                                   enc_frames=enc_s or 0)
            st = RequestState(req=req, slot=slot, pos=n, out=[first])
            done = first == req.eos_id or len(st.out) >= req.max_new
            self._set_lane(slot, token=first, pos=n, enc_len=enc_s or 0,
                           eos=req.eos_id, max_new=req.max_new, n_out=1,
                           active=not done)
            if done:
                st.done = True
                self._free_slot(slot)
            else:
                self.active[slot] = st
            return st

    # ---------------------------------------------------- streaming audio
    def open_stream(self, req: StreamingAudioRequest
                    ) -> Optional[RequestState]:
        """Allocate a slot for a streaming audio request; None if the
        pool is full. No prefill happens yet — the first ``stream_feed``
        anchors the prompt against the first chunk's states."""
        if not isinstance(req, StreamingAudioRequest):
            raise ValueError(f"request {req.uid}: open_stream takes a "
                             f"StreamingAudioRequest")
        err = self.validate(req)
        if err is not None:
            raise RejectionError(err)
        if not self.free:
            return None
        slot = self.free.pop()
        if self.paged:
            # register the lane with empty page sets — cross pages are
            # allocated per chunk in stream_feed, self pages at the
            # first anchor (when the prompt+budget extent is known)
            self.pages.admit_stream_lane(slot)
        self.lanestate.reserve(
            slot, self.spec, n_tokens=len(req.tokens) + req.max_new)
        st = RequestState(req=req, slot=slot, pos=0, out=[])
        self._streams[slot] = _StreamState(states=[])
        return st

    def stream_feed(self, st: RequestState, frames) -> RequestState:
        """Feed one chunk of frame embeddings ((s, d_model)) to an open
        stream: encode the chunk (block-diagonal — its states never
        change as more audio arrives), extend the slot's cached cross
        K/V in place, and grow the lane's ``enc_lens`` so the very next
        decode tick attends the new audio. Appends a partial-hypothesis
        snapshot to ``st.partials``."""
        with tracing.span("engine.stream_feed"):
            slot = st.slot
            ss = self._streams[slot]
            fr = jnp.asarray(np.asarray(frames, np.float32))[None]
            s_new = int(fr.shape[1])
            if ss.n_frames + s_new > self.enc_len:
                raise RejectionError(Rejection(
                    RejectCode.ENC_OVERFLOW,
                    f"request {st.req.uid}: stream overflows the pool "
                    f"enc_len {self.enc_len} ({ss.n_frames}+{s_new})"))
            with use_context(self.dispatch_ctx):
                states = self._encode(self.params, fr)
            ss.states.append(states)
            first_feed = not ss.anchored
            if self.paged:
                # grow the lane's cross pages to cover the new chunk before
                # anything writes it (the first feed's pages are written by
                # the anchor prefill, later feeds by the extend jit)
                try:
                    phys, off = self.pages.extend_cross(slot, ss.n_frames,
                                                        s_new)
                except PageAllocError as e:
                    raise RejectionError(Rejection(
                        RejectCode.POOL_EXHAUSTED,
                        f"request {st.req.uid}: cross-KV page pool "
                        f"exhausted mid-stream ({e})"))
            if not first_feed:
                # incremental extension: project the new states through each
                # decoder layer's cross K/V and write them after the
                # already-cached positions (quantizing for a q8_0 pool; the
                # pool buffer is donated — an in-place plane write).
                with use_context(self.dispatch_ctx), _quiet_donation():
                    k, v = self._cross_kv(self.params, states)
                    if self.paged:
                        self.cache = self._extend(
                            self.cache, k, v, jnp.asarray(phys, jnp.int32),
                            jnp.asarray(off, jnp.int32))
                    else:
                        self.cache = self._extend(self.cache, k, v, slot,
                                                  ss.n_frames)
            ss.n_frames += s_new
            self.lanestate.extend_cross(slot, s_new)
            if first_feed:
                self._anchor(st, ss, final=False)
            else:
                self._enc_lens = self._enc_lens.at[slot].set(ss.n_frames)
            st.partials.append(list(st.out))
            return st

    def stream_finalize(self, st: RequestState) -> RequestState:
        """End of audio: re-anchor the prompt against the *full* encoder
        states (one bucketed prefill — the encoder work is NOT redone),
        so the final transcript is token-identical to one-shot serving
        of the same chunked audio. The mid-stream hypothesis is kept as
        the last entry of ``st.partials``."""
        slot = st.slot
        ss = self._streams.pop(slot)
        if st.out:
            st.partials.append(list(st.out))
        self.active.pop(slot, None)
        self._anchor(st, ss, final=True)
        return st

    def _anchor(self, st: RequestState, ss: _StreamState,
                final: bool) -> None:
        """Prompt prefill for a streaming lane over the states fed so
        far (the same jitted states-prefill the one-shot path uses; the
        scatter re-writes the slot's cross planes with values identical
        to the incremental extension)."""
        req, slot = st.req, st.slot
        n = len(req.tokens)
        states = ss.states[0] if len(ss.states) == 1 \
            else jnp.concatenate(ss.states, axis=1)
        bucket = min(_bucket(n), self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = req.tokens
        if self.paged:
            lane = self.pages.lanes[slot]
            if not lane.self_pages:
                # first anchor: allocate the lane's full self-KV extent
                # (prompt + decode budget) so no tick ever allocates
                try:
                    self.pages.alloc_self(
                        slot, n, req.max_new + (self.spec_k - 1
                                                if self.spec_k else 0))
                except PageAllocError as e:
                    raise RejectionError(Rejection(
                        RejectCode.POOL_EXHAUSTED,
                        f"request {req.uid}: self-KV page pool "
                        f"exhausted at anchor ({e})"))
            pv_self = jnp.asarray(self.pages.self_table.row(slot),
                                  jnp.int32)
            pv_cross = jnp.asarray(self.pages.cross_table.row(slot),
                                   jnp.int32)
            with use_context(self.dispatch_ctx), _quiet_donation():
                first, self.cache = self._prefill_fn(
                    bucket, int(states.shape[1]), from_states=True)(
                        self.params, self.cache, jnp.asarray(toks), n,
                        pv_self, pv_cross, states)
        else:
            with use_context(self.dispatch_ctx), _quiet_donation():
                first, self.cache = self._prefill_fn(
                    bucket, int(states.shape[1]), from_states=True)(
                        self.params, self.cache, jnp.asarray(toks), n,
                        slot, states)
        first = int(first)   # scalar fetch, as in admit()
        self._generated += 1
        ss.anchored = True
        st.out = [first]
        st.pos = n
        finished = first == req.eos_id or req.max_new <= 1
        self._set_lane(slot, token=first, pos=n, enc_len=ss.n_frames,
                       eos=req.eos_id, max_new=req.max_new, n_out=1,
                       active=not finished)
        if final and finished:
            st.done = True
            self._free_slot(slot)
        elif not finished:
            self.active[slot] = st
        # mid-stream + finished: lane pauses (stays allocated, resumes
        # at the next anchor)

    def encode_chunks(self, chunks) -> jnp.ndarray:
        """Encode a list of frame-embedding chunks through the engine's
        jitted per-size encoder — the exact functions ``stream_feed``
        uses — and concatenate the states (1, sum(s_i), d_model). The
        one-shot ``transcribe`` path uses this so its states are
        bit-identical to the streaming path's."""
        outs = []
        with use_context(self.dispatch_ctx):
            for c in chunks:
                fr = jnp.asarray(np.asarray(c, np.float32))[None]
                outs.append(self._encode(self.params, fr))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    @property
    def n_streams(self) -> int:
        """Open (not yet finalized) audio streams."""
        return len(self._streams)

    # ------------------------------------------------------------------
    def step_begin(self, k: Optional[int] = None) -> Optional[PendingTick]:
        """Dispatch one fused decode tick and return immediately —
        the device runs the ``k``-step scan while the host keeps
        working (JAX async dispatch). The engine's cache/lane-state
        references already point at the tick's (still materializing)
        outputs; the returned ``PendingTick`` holds the un-fetched
        token/emit blocks for ``step_fetch``/``step_replay``. Returns
        None when no lane is active (nothing to dispatch).

        This is the gateway's double-buffering hook: between
        ``step_begin`` and ``step_fetch`` the host resolves futures,
        drains streams, and picks the next tick's admissions while the
        device decodes."""
        if not self.active:
            return None
        k = self.decode_block if k is None else int(k)
        if k < 1:   # a 0-length scan would emit nothing and never drain
            raise ValueError(f"decode block must be >= 1, got {k}")
        if self.spec_k and k % self.spec_k:
            raise ValueError(f"decode block ({k}) must be a multiple of "
                             f"spec_k ({self.spec_k})")
        with tracing.span("engine.dispatch") as sp, \
                use_context(self.dispatch_ctx), _quiet_donation():
            if sp is not None:
                sp.attrs.update(lanes=len(self.active), k=k)
            fn = self._decode_fn(k)
            if self.paged:
                # the tick donates the device tables and returns them
                # aliased (it never remaps pages); re-adopt them guarded
                # by the host tables' version so a concurrent admit
                # (between step_begin and step_fetch) wins
                sv = self.pages.self_table.version
                cv = self.pages.cross_table.version
                tables = {"self": self.pages.self_table.device(),
                          "cross": self.pages.cross_table.device()}
                (tok_blk, emit_blk, self.cache, tables, self._tokens,
                 self._pos, self._lane_active, self._lane_out) = fn(
                    self.params, self.cache, tables, self._tokens,
                    self._pos, self._lane_active, self._lane_out,
                    self._enc_lens, self._lane_eos, self._lane_max)
                self.pages.self_table.adopt(tables["self"], sv)
                self.pages.cross_table.adopt(tables["cross"], cv)
            else:
                (tok_blk, emit_blk, self.cache, self._tokens, self._pos,
                 self._lane_active, self._lane_out) = fn(
                    self.params, self.cache, self._tokens, self._pos,
                    self._lane_active, self._lane_out, self._enc_lens,
                    self._lane_eos, self._lane_max)
        return PendingTick(k=k, tok_blk=tok_blk, emit_blk=emit_blk)

    def step_fetch(self, pending: PendingTick):
        """THE host sync of a tick: block until the device finishes and
        fetch the ``(k, n_slots)`` token block + emit mask in one
        device_get. Safe to call off-thread (the gateway fetches in an
        executor so its event loop stays live during the device wait)."""
        with tracing.span("engine.fetch"):
            tok_blk, emit_blk = jax.device_get(
                (pending.tok_blk, pending.emit_blk))
            self._host_syncs += 1
            self._ticks += 1
            emitted = int(emit_blk.sum())
            self._generated += emitted
            if self.spec_k:
                # a spec tick executes rounds, not plain steps: each round
                # is spec_k - 1 draft forwards + ONE multi-query verify
                # forward of the full model
                rounds = pending.k // self.spec_k
                self._spec_rounds += rounds
                self._draft_steps += rounds * (self.spec_k - 1)
                self._verify_steps += rounds
                self._spec_emitted += emitted
                # (round, lane) pairs that emitted at all — the denominator
                # of the draft-acceptance rate
                live = emit_blk.reshape(rounds, self.spec_k, -1).any(axis=1)
                self._spec_live_rounds += int(live.sum())
            else:
                self._decode_steps += pending.k
            return tok_blk, emit_blk

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft tokens the verify forward accepted so far
        (0.0 if no speculative round has emitted yet). Each live round
        emits 1 verified token plus ``accepted`` drafts out of
        ``spec_k - 1``."""
        if not self._spec_live_rounds or self.spec_k < 2:
            return 0.0
        accepted = self._spec_emitted - self._spec_live_rounds
        return accepted / (self._spec_live_rounds * (self.spec_k - 1))

    def step_replay(self, pending: PendingTick, tok_blk,
                    emit_blk) -> list[RequestState]:
        """Host replay of a fetched tick: append emitted tokens to each
        lane's ``RequestState``, free finished slots, pause streaming
        lanes — the bookkeeping no jit can do."""
        with tracing.span("engine.replay"):
            k = pending.k
            finished = []
            for slot, st in list(self.active.items()):
                for j in range(k):
                    if not emit_blk[j, slot]:
                        # plain ticks freeze lanes prefix-contiguously, but
                        # a speculative round that accepts m < spec_k tokens
                        # leaves a gap before the next round's rows — keep
                        # scanning the whole block
                        continue
                    tok = int(tok_blk[j, slot])
                    st.out.append(tok)
                    st.pos += 1
                    # replay of the on-device stop condition, token for token
                    if tok == st.req.eos_id or len(st.out) >= st.req.max_new \
                            or st.pos >= self.max_len - 1:
                        if slot in self._streams:
                            # mid-stream hypothesis complete: pause the lane
                            # (keep the slot and its growing encoder cache);
                            # stream_finalize re-anchors and decodes the
                            # final transcript.
                            self.active.pop(slot)
                        else:
                            st.done = True
                            self.active.pop(slot)
                            self._free_slot(slot)
                            finished.append(st)
                        break
                if self.paged:
                    # advance the lane's valid-token extent (fragmentation
                    # accounting only; allocation already covered max_new;
                    # no-op for lanes freed above)
                    self.pages.note_len(slot, st.pos)
            return finished

    def step_end(self, pending: Optional[PendingTick]
                 ) -> list[RequestState]:
        """Fetch + replay a dispatched tick (None — from an idle
        ``step_begin`` — is a no-op)."""
        if pending is None:
            return []
        tok_blk, emit_blk = self.step_fetch(pending)
        return self.step_replay(pending, tok_blk, emit_blk)

    def step(self, k: Optional[int] = None) -> list[RequestState]:
        """One fused decode tick over the whole pool: ``k`` (default
        ``decode_block``) decode steps in a single donated jit, then
        exactly one host sync — the ``(k, n_slots)`` token block and its
        emit mask — to run the Python bookkeeping (append to
        ``RequestState.out``, free finished slots, pause streaming
        lanes). Token-identical to ``k`` calls of ``step(1)``.
        Equivalent to ``step_end(step_begin(k))``."""
        return self.step_end(self.step_begin(k))

    def abort(self, st: RequestState, code: RejectCode = None,
              message: Optional[str] = None) -> None:
        """Evict an in-flight request (client cancelled / timed out):
        close its open stream, deactivate its lane, and zero+free the
        slot so the next admission reuses it cleanly. Safe on requests
        that already completed (no-op)."""
        slot = st.slot
        if st.done or slot < 0:
            return
        self._streams.pop(slot, None)
        self.active.pop(slot, None)
        if slot not in self.free:
            self._free_slot(slot)
        st.done = True
        st.error_code = code or RejectCode.CANCELLED
        st.error = message or \
            f"request {st.req.uid} {st.error_code.value}"

    def _free_slot(self, slot: int) -> None:
        """Return a lane to the pool and zero its decode inputs — a
        parked lane then attends exactly one (stale but harmless)
        position instead of its full dead context, and its emit mask
        stays off."""
        if self.paged:
            # drop page refs and point the lane's table rows at the
            # scratch page (any in-flight device write for this lane
            # lands there, never on a page another lane now owns)
            self.pages.free_lane(slot)
        if self.lanestate.holds(slot):
            self.lanestate.release(slot)
        self.free.append(slot)
        self._set_lane(slot, token=0, pos=0, enc_len=0, eos=0, max_new=0,
                       n_out=0, active=False)

    @property
    def n_active(self) -> int:
        return len(self.active)

    # ------------------------------------------------------------------
    def cache_report(self) -> dict:
        """Cache footprint / decode-traffic accounting.

        ``bytes_per_step`` is the full-pool cache stream of one decode
        step (this dense implementation reads every cache position and
        masks after the dot — exactly the paper's LOAD term; a fused
        tick streams it ``decode_block`` times). Recurrent/routing
        state (LaneStateSpec) is read AND fully rewritten every step,
        so it streams twice per step — constant in sequence length,
        which is the whole O(1)-state memory story; pure-KV engines see
        a zero delta. The analytic per-token figure uses
        ``core.quantize.stored_bytes`` under the paper's dense packing
        (C3)."""
        kv_bytes, state_bytes = _cache_bytes(self.cache)
        cfg = self.model.cfg
        dt = self.cache_dtype if self.cache_dtype in QUANT_TIERS \
            else "bf16"
        per_tok = 2 * cfg.n_layers * stored_bytes(
            (cfg.n_kv_heads, cfg.head_dim), dt)
        state_per_step = 2 * state_bytes
        out = {
            "cache_dtype": self.cache_dtype,
            "family": self.spec.family,
            "state_kinds": list(self.spec.state_kinds),
            "kv_bytes_total": kv_bytes,
            "state_bytes_total": state_bytes,
            "state_bytes_per_step": state_per_step,
            "bytes_per_step": kv_bytes + state_per_step,
            "self_kv_bytes_per_token": per_tok,
            "traffic_ratio_vs_bf16":
                cache_traffic_ratio() if self.cache_dtype == "q8_0"
                else cache_traffic_ratio_q4()
                if self.cache_dtype == "q4_0" else 1.0,
        }
        if self.paged:
            # paged pools stream only MAPPED pages per step (the gather
            # reads through the tables), so the decode LOAD term — and
            # the energy model built on it — prices actual resident
            # request bytes, not n_slots x max_len padding.
            rep = self.pages.report()
            layers = self.cache["layers"]
            sb = sum(int(l.nbytes) for l in jax.tree.leaves(layers["self"]))
            cb = sum(int(l.nbytes)
                     for l in jax.tree.leaves(layers["cross"]))
            spb = sb // self.pages.self_pool.n_pages
            cpb = cb // self.pages.cross_pool.n_pages
            resident = (rep["self"]["pages_in_use"] * spb
                        + rep["cross"]["pages_in_use"] * cpb)
            out["paging"] = {
                **rep,
                "self_page_bytes": spb,
                "cross_page_bytes": cpb,
                "resident_kv_bytes": resident,
            }
            out["bytes_per_step"] = resident + state_per_step
        return out

    def paging_report(self) -> dict:
        """Page-pool occupancy / fragmentation / prefix-sharing stats
        (``repro.paging`` accounting; paged engines only)."""
        if not self.paged:
            raise ValueError("paging_report() requires paged=True")
        return self.pages.report()

    def lane_report(self) -> dict:
        """The host-side lane-state ledger (``LaneStatePool.report``):
        which state kinds each live lane holds, with extents."""
        return self.lanestate.report()

    def routing_report(self) -> dict:
        """MoE engines: fetch the per-lane expert-routing counters the
        decode/prefill jits accumulate in the cache's "routing" planes.
        A diagnostic host sync (inventoried, NOT on the per-tick path):
        counters count *executed* top-k assignments — the fused tick
        decodes every slot, parked lanes included, so this is the
        device-work / expert-load picture the energy model prices, not
        a per-request billing meter."""
        if not self.spec.moe_experts:
            raise ValueError(
                f"routing_report() needs an MoE model; "
                f"{self.model.cfg.name} declares no routing state")
        planes = []

        def grab(tree):
            if isinstance(tree, dict):
                for key, sub in tree.items():
                    if key == "routing":
                        planes.append(sub)
                    else:
                        grab(sub)

        grab(self.cache)
        stacked = jax.device_get(planes)   # [(n_layers_i, n_slots, E)]
        per_lane = sum(p.sum(axis=0) for p in stacked)  # (n_slots, E)
        totals = per_lane.sum(axis=0)
        return {
            "n_experts": self.spec.moe_experts,
            "top_k": self.spec.moe_top_k,
            "moe_layers": sum(int(p.shape[0]) for p in stacked),
            "per_lane": per_lane.tolist(),
            "per_expert": totals.tolist(),
            "executed_assignments": int(totals.sum()),
        }

    def page_headroom(self) -> float:
        """Free-page fraction of the tighter pool (1.0 for slot
        engines) — the gateway's load-shed signal: when this drops
        below its threshold, BATCH-class work is shed first so
        interactive admissions keep finding pages."""
        if not self.paged:
            return 1.0
        sp, cp = self.pages.self_pool, self.pages.cross_pool
        return min(sp.free_pages / max(sp.n_pages - 1, 1),
                   cp.free_pages / max(cp.n_pages - 1, 1))

    def dispatch_report(self) -> dict:
        """Kernel-routing counters (trace-time, keyed (op, decision,
        backend); process-global — reset via api.reset_dispatch_log())
        plus the engine's cache footprint/traffic accounting."""
        return {
            "counters": dict(dispatch_counters()),
            "cache": self.cache_report(),
        }

    # ------------------------------------------------------------------
    def reset_serve_stats(self) -> None:
        """Zero the serve-energy accounting (executed ticks / decode
        steps / emitted tokens / host syncs) so the next
        ``energy_report()`` prices only work from this point on.
        Per-call reports on a reused engine
        (``repro.transcribe(engine=...)``) reset before serving."""
        self._ticks = 0
        self._decode_steps = 0
        self._generated = 0
        self._host_syncs = 0
        self._draft_steps = 0
        self._verify_steps = 0
        self._spec_rounds = 0
        self._spec_emitted = 0
        self._spec_live_rounds = 0

    def _param_stats(self) -> tuple[int, int]:
        """(element count, stored bytes) of the served parameters."""
        leaves = jax.tree.leaves(self.params)
        return (sum(int(l.size) for l in leaves),
                sum(int(l.nbytes) for l in leaves))

    def energy_report(self, kernel: str = "fp16") -> dict:
        """Joules-per-token / PDP accounting for the serve so far on the
        engine's platform — the paper's headline metric (Eq. 1), live on
        the serving path.

        The decode phase dominates serving energy, and every decode
        step streams the weights plus the whole KV pool through the
        cache matvec; the model here is the platform roofline over
        exactly those terms:

        * memory: ``decode_steps x (weight_bytes + cache bytes/step)``
          at the platform's DRAM/HBM bandwidth — a fused tick executes
          ``decode_block`` steps, so the stream is priced per *step*,
          never per host tick (joules/token stays correct when
          ``_ticks`` advances once per ``decode_block`` tokens),
        * compute: ``2 x N_params`` FLOPs per generated token at the
          platform's ``kernel``-dtype rate,
        * modeled latency = max(memory, compute) (the binding resource),
        * power: the platform ``PowerModel`` — Table-II curve targets
          interpolate at their LMM size for the ``kernel`` family
          ("fp16" | "q8_0" — the served weight family, *not* the cache
          dtype); flat targets scale nominal power by compute
          utilization.

        The dispatch trace records stamped with this platform fold in as
        the ACCEL/HOST mix (``accel_flops_share``); cache traffic folds
        in via ``cache_report()`` — so a q8_0 cache pool shows up
        directly as a smaller ``cache_energy_j``.
        """
        if self.platform is None:
            raise ValueError(
                "energy_report() needs a platform: construct the engine "
                "with ServeEngine(..., platform='imax3-28nm/32k')")
        p = self.platform
        cache = self.cache_report()
        n_elems, weight_bytes = self._param_stats()
        ticks = self._ticks
        steps = self._decode_steps
        tokens = self._generated
        cbs = cache["bytes_per_step"]
        cache_bytes = steps * cbs
        stream_bytes = steps * weight_bytes + cache_bytes
        flops = 2.0 * n_elems * tokens
        spec = None
        if self.spec_k:
            # speculative roofline: every draft forward streams the
            # (smaller) draft weights + the cache once; every verify
            # forward streams the full weights + the cache ONCE for all
            # spec_k positions — that amortization is the whole win
            d_leaves = jax.tree.leaves(self.draft_params)
            d_elems = sum(int(l.size) for l in d_leaves)
            d_bytes = sum(int(l.nbytes) for l in d_leaves)
            cache_bytes += (self._draft_steps + self._verify_steps) * cbs
            stream_bytes = cache_bytes \
                + steps * weight_bytes \
                + self._draft_steps * d_bytes \
                + self._verify_steps * weight_bytes
            flops = 2.0 * n_elems * (steps
                                     + self._verify_steps * self.spec_k) \
                + 2.0 * d_elems * self._draft_steps
            spec = {
                "spec_k": self.spec_k,
                "draft_dtype": self.draft_dtype,
                "rounds": self._spec_rounds,
                "draft_steps": self._draft_steps,
                "verify_steps": self._verify_steps,
                "acceptance_rate": self.acceptance_rate,
                "draft_weight_bytes": d_bytes,
            }
        bw = max(p.memory.main_bw, 1e-9)
        rate = p.peak_flops("q8_0" if kernel == "q8_0" else "f16")
        t_mem = stream_bytes / bw
        t_comp = flops / rate
        latency_s = max(t_mem, t_comp)
        util = t_comp / latency_s if latency_s > 0 else 0.0
        power_w = p.power.power(kernel, p.memory.local_bytes or None,
                                util=util)
        energy_j = latency_s * power_w
        # ACCEL/HOST mix from the trace records THIS engine produced
        # (its context's unique tag); a caller-supplied dispatch_ctx has
        # no engine tag, so fall back to platform-name attribution
        tag = self.dispatch_ctx.tag if self.dispatch_ctx else None
        if tag:
            recs = [r for r in dispatch_trace() if r.tag == tag]
        else:
            recs = [r for r in dispatch_trace() if r.platform == p.name]
        accel_flops = sum(r.spec.flops for r in recs
                          if r.decision == "accel")
        trace_flops = sum(r.spec.flops for r in recs)
        return {
            "platform": p.name,
            "kernel": kernel,
            "cache_dtype": self.cache_dtype,
            "ticks": ticks,
            "decode_steps": steps,
            "decode_block": self.decode_block,
            "host_syncs": self._host_syncs,
            "tokens": tokens,
            "weight_bytes": weight_bytes,
            "cache_bytes_per_step": cache["bytes_per_step"],
            "stream_bytes_total": stream_bytes,
            "modeled_flops": flops,
            "memory_s": t_mem,
            "compute_s": t_comp,
            "latency_s": latency_s,
            "bound": "memory" if t_mem >= t_comp else "compute",
            "power_w": power_w,
            "pdp_j": energy_j,
            "joules_per_token": energy_j / max(tokens, 1),
            "cache_energy_j": (cache_bytes / bw) * power_w,
            "accel_flops_share":
                accel_flops / trace_flops if trace_flops else 0.0,
            "trace_records": len(recs),
            "modeled_tokens_per_s":
                tokens / latency_s if latency_s > 0 else 0.0,
            **({"speculative": spec} if spec else {}),
        }


def _cache_bytes(tree) -> tuple[int, int]:
    """(KV-plane bytes, recurrent-state bytes) of a cache pytree."""
    if isinstance(tree, dict):
        if set(tree) in ({"k", "v"}, {"kq", "ks", "vq", "vs"},
                         {"kp", "ks", "vp", "vs"}):
            return sum(int(l.nbytes) for l in jax.tree.leaves(tree)), 0
        kv = st = 0
        for sub in tree.values():
            a, b = _cache_bytes(sub)
            kv += a
            st += b
        return kv, st
    return 0, sum(int(l.nbytes) for l in jax.tree.leaves(tree))


def _write_lane(lanes: tuple, row) -> tuple:
    """One lane of the decode state written: ``lanes`` is (tokens, pos,
    enc_lens, eos, max_new, n_out, active), ``row`` the int32 vector
    [slot, token, pos, enc_len, eos, max_new, n_out, active]."""
    tokens, pos, enc_lens, eos, max_new, n_out, active = lanes
    slot = row[0]
    return (tokens.at[slot, 0].set(row[1]), pos.at[slot].set(row[2]),
            enc_lens.at[slot].set(row[3]), eos.at[slot].set(row[4]),
            max_new.at[slot].set(row[5]), n_out.at[slot].set(row[6]),
            active.at[slot].set(row[7] != 0))


def _scatter_slot(pool: Any, one: Any, slot) -> Any:
    """Write a batch-1 cache pytree into lane ``slot`` of the pool.

    Every cache leaf is (stacked_layers, B, ...) — transformer segments,
    encdec layers, and tails all stack with jnp.broadcast_to /scan — so
    the slot axis is axis 1 throughout. ``slot`` may be a traced scalar
    (the prefill jit passes it dynamically, so one compile covers every
    lane)."""
    def scat(p, o):
        assert p.shape[0] == o.shape[0] and o.shape[1] == 1, (p.shape, o.shape)
        return jax.lax.dynamic_update_slice_in_dim(
            p, o.astype(p.dtype), slot, axis=1)
    return jax.tree.map(scat, pool, one)


def _quantize_cross_planes(k, v, tier: str) -> dict:
    """Chunk cross-K/V -> the tier's plane dict (pre-write)."""
    if tier == "q4_0":
        kt = quantize_q4_0(k, axis=-1)
        vt = quantize_q4_0(v, axis=-1)
        return {"kp": kt.q, "ks": kt.scale, "vp": vt.q, "vs": vt.scale}
    kt = quantize_q8_0(k, axis=-1)
    vt = quantize_q8_0(v, axis=-1)
    return {"kq": kt.q, "ks": kt.scale, "vq": vt.q, "vs": vt.scale}


def _extend_cross_cache(cache: dict, k, v, slot, offset, *,
                        tier: Optional[str]) -> dict:
    """Write new cross-K/V positions ((L, 1, s_new, Hkv, ·)) into lane
    ``slot`` of the pool's cross cache at ``offset`` (streaming audio:
    the chunk's planes land after the already-cached positions). Jitted
    by the engine with the pool donated — an in-place plane write."""
    cross = cache["layers"]["cross"]

    def dus(plane, new):
        return jax.lax.dynamic_update_slice(
            plane, new.astype(plane.dtype), (0, slot, offset, 0, 0))

    if tier:
        planes = _quantize_cross_planes(k, v, tier)
        new_cross = {key: dus(cross[key], val)
                     for key, val in planes.items()}
    else:
        new_cross = {"k": dus(cross["k"], k), "v": dus(cross["v"], v)}
    return {"layers": {**cache["layers"], "cross": new_cross}}


def _enc_digest(x, kind: str) -> str:
    """Content key of a request's encoder input for paged prefix
    sharing. Decoder self-K/V flows through cross-attention, so shared
    prompt pages are only valid between lanes with identical audio —
    the digest is part of the self-prefix key, not just the cross key.
    ``kind`` ("frames"/"states") keeps the two input encodings from
    ever colliding."""
    arr = np.asarray(x)
    return hashlib.sha1(kind.encode() + arr.tobytes()).hexdigest()


def _scatter_pages(pool: Any, one: Any, pv_self, pv_cross,
                   page_size: int) -> Any:
    """Write a batch-1 dense cache pytree into a lane's physical pages.

    Each dense leaf ``(L, 1, S, ...)`` is reshaped into page rows
    ``(L, S // P, P, ...)`` and scattered at the lane's page vector
    (``pv`` covers the full logical extent: mapped pages first, then
    the scratch page, which absorbs the bucket padding — duplicate
    scratch indices are benign, last-write-wins over garbage). Shared
    prefix pages are rewritten with bit-identical content (prefill is
    deterministic), so the scatter never corrupts another lane."""
    def scat(plane, dense, pv):
        lead, s = dense.shape[0], dense.shape[2]
        rows = dense[:, 0].reshape(
            (lead, s // page_size, page_size) + dense.shape[3:])
        return plane.at[:, pv].set(rows.astype(plane.dtype))

    layers, dense_layers = pool["layers"], one["layers"]
    new = {kind: {key: scat(layers[kind][key], dense_layers[kind][key],
                            pv)
                  for key in layers[kind]}
           for kind, pv in (("self", pv_self), ("cross", pv_cross))}
    return {"layers": new}


def _extend_paged_cross_cache(cache: dict, k, v, phys, off, *,
                              tier: Optional[str]) -> dict:
    """Paged variant of ``_extend_cross_cache``: the chunk's s_new new
    positions land at ``(layer, phys[i], off[i])`` in the shared cross
    planes (gather targets from ``PagedKV.extend_cross``). Jitted with
    the pool donated — an in-place plane write; one compile per
    distinct chunk length."""
    cross = cache["layers"]["cross"]

    def scat(plane, new):
        return plane.at[:, phys, off].set(new[:, 0].astype(plane.dtype))

    if tier:
        planes = _quantize_cross_planes(k, v, tier)
        new_cross = {key: scat(cross[key], val)
                     for key, val in planes.items()}
    else:
        new_cross = {"k": scat(cross["k"], k), "v": scat(cross["v"], v)}
    return {"layers": {**cache["layers"], "cross": new_cross}}
