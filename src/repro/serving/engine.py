"""Serving engines: continuous batching (default) + static-batch reference.

Serving-side integration of the paper: with ``cfg.wta_head`` the sampler is
the WTA stochastic SoftMax circuit — per emitted token, T comparator-bank
decision trials vote and the majority wins (§III-B/C).  Repeated-vote
majority is exactly the paper's accuracy-recovery mechanism (Fig. 6), here
applied to LM decoding; greedy argmax is the digital baseline.

``ServingEngine`` is a continuous-batching engine: a slot-based scheduler
(`repro.serving.scheduler`) admits queued requests into free slots of a
live decode batch.  Under the paged layout, prefill is a CHUNKED,
INTERLEAVED phase: an admission enqueues a prefill job (prompt left-padded
to a compile-size bucket) and the engine computes at most
``ServeConfig.prefill_chunk`` suffix tokens per tick between batched
decode steps — long prompts never stall the in-flight decodes for more
than one chunk's worth of work, and a partial-prefix hit starts its job
mid-prompt (see below).  The dense layout keeps the monolithic
one-request prefill as the byte-identity oracle.  Finished requests (EOS
or per-request ``max_new_tokens``) are evicted and their slot refilled
mid-flight, which is what lifts slot occupancy over static batching on
mixed-length traces.

The KV cache is **paged** by default (``ServeConfig.kv_layout``): a global
pool of fixed-size blocks plus a per-slot block table, so cache capacity is
shared across slots and a decode step only touches the blocks a request has
actually filled — O(blocks·block_size) attention work per token instead of
O(max_len).  Blocks are taken from a free-list allocator at admission
(covering the whole prompt+budget, so a request can never starve
mid-decode), returned at eviction, and pool exhaustion back-pressures
admission (the queue head waits, FIFO preserved).  ``kv_layout="dense"``
keeps the PR-1 per-slot ``max_len`` window as the equivalence oracle:
greedy decode is byte-identical between the two layouts
(tests/test_serving.py).

With ``ModelConfig.kv_cache_dtype="int8"`` the paged pool holds
stochastically rounded int8 codes + per-(page, slot-in-page, head) scale
planes — half the decode HBM bytes per token, dequant fused into the
paged-attention math, and ``num_kv_blocks`` (a native-dtype memory budget)
buys twice the pages, so admission takes ~2x the requests at equal budget
(docs/serving.md §"Quantized KV pool").

Prefix sharing (``ServeConfig.enable_prefix_sharing``, paged only): each
admission chains content hashes over its padded prompt's blocks and maps
the deepest resident match into its block table (refcount bump in the
allocator's prefix index) instead of re-prefilling it.  A *full* match
skips prefill entirely (first token sampled from the original prefill's
stored last-token logits, stored O(1) state leaves inserted).  A
*partial* match prefills ONLY the suffix: the job starts at the resume
point and its chunks attend into the shared paged K/V through the
prefix-aware chunked-prefill kernel — attention-only families resume at
the full matched block depth, recurrent/SSM families at the deepest chunk
boundary whose state snapshot is stashed in the index.  The first decode
write into a still-shared block copy-on-write forks it onto a spare page
reserved at admission; pages return to the free list only at refcount
zero.  int8 pools stay shareable because block quantization seeds derive
from block CONTENT (chain hash), not the request id (docs/serving.md
§"Prefix sharing & copy-on-write", §"Partial-prefix prefill & chunked
scheduling").

WTA sampling stays independent per request: every slot carries the key
``fold_in(base_key, rid)`` and a step counter, so a request's vote noise is
a function of (its rid, its token index) only — invariant to batch
composition.  ``StaticServingEngine`` keeps the old static-batch semantics
(whole batch prefilled together, slots held until the last request ends) as
the baseline that benchmarks and equivalence tests compare against.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import backend as BK
from repro.kernels import ops as KOPS
from repro.launch import specs as SP
from repro.models import ModelConfig, get_model_fns
from repro.serving.scheduler import (
    BlockAllocator,
    Request,
    RequestState,
    Scheduler,
    left_pad,
    prefix_block_hashes,
)


def _pctl(vals: Sequence[float], q: float) -> float:
    """Percentile helper tolerant of empty samples (metrics views)."""
    return float(np.percentile(np.asarray(vals), q)) if len(vals) else 0.0


def _default_buckets(max_len: int) -> tuple[int, ...]:
    out, b = [], 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


@dataclasses.dataclass
class DegradationPolicy:
    """Graceful-degradation ladder under sustained fault pressure.

    The engine tracks *detection events* per tick (canary failures +
    logit-sanity evictions).  ``trip_after`` consecutive dirty ticks
    escalate one rung; ``recover_after`` consecutive clean canary PASSES
    de-escalate one rung (so without a canary configured, degradation is
    one-way — there is no evidence the substrate recovered).  Rungs, in
    order, trade throughput for integrity:

    * level 0 — healthy, all features on;
    * level 1 — speculative decoding disabled (a drafted run multiplies
      the blast radius of one bad logit row by k);
    * level 2 — WTA redundant reads raised to ``redundant_reads``
      (majority voting over comparator re-reads, priced in the energy
      accounting);
    * level 3 — admissions shed: queued requests with priority strictly
      less urgent than ``shed_priority_above`` wait while interactive
      traffic keeps flowing.

    Every transition (either direction) is recorded in
    ``ServingMetrics.degraded_transitions`` with the tick and cause.
    """

    trip_after: int = 2        # consecutive dirty ticks per escalation
    recover_after: int = 3     # consecutive clean canary passes per rung
    redundant_reads: int = 3   # R at level >= 2 (majority vote)
    shed_priority_above: int = 0  # level 3: shed priority > this


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8          # decode slots
    max_new_tokens: int = 32    # default per-request budget
    max_len: int = 512          # per-request capacity (prompt + generated)
    eos_token: int = -1         # -1: never stop early
    seed: int = 0
    # prompt lengths are left-padded up to the next bucket so prefill
    # compiles once per bucket, not once per distinct prompt length.
    prefill_buckets: tuple[int, ...] = ()
    # KV cache layout: "paged" (block pool + per-slot block table, the
    # default) or "dense" (per-slot max_len window, the PR-1 oracle).
    kv_layout: str = "paged"
    kv_block_size: int = 16     # tokens per KV block (paged layout)
    # total pool size in blocks; 0 → dense-parity capacity
    # (max_batch · ceil(max_len / block) + 1 trash block).  Set it lower to
    # shrink cache memory — admission back-pressures when the pool runs dry.
    num_kv_blocks: int = 0
    # paged layout only: admissions match their padded prompt's blocks
    # against resident blocks (content-hash prefix index) and map the hits
    # into their block table instead of re-prefilling; the first write into
    # a still-shared block copy-on-write forks it.  Greedy decode is
    # byte-identical with sharing on vs off (tests/test_serving.py); turn
    # it off to isolate raw pool behavior (capacity benchmarks).
    enable_prefix_sharing: bool = True
    # paged layout only: at most this many prefill tokens are COMPUTED per
    # engine tick, between decode steps — a long prompt prefills as a
    # sequence of suffix chunks while the in-flight slots keep decoding,
    # bounding the decode-latency jitter a monolithic bucket prefill would
    # inject.  0 (the default) computes the whole bucket as one chunk.
    # Must be a positive multiple of kv_block_size when set; chunk
    # boundaries are also the resume grid for partial-prefix hits of
    # recurrent/SSM families (their boundary states are stashed in the
    # prefix index), so smaller chunks = finer-grained prefix reuse for
    # stateful models, at more (bucket, chunk) compile pairs.
    prefill_chunk: int = 0
    # paged layout only: when a higher-priority arrival cannot reserve
    # blocks (or a slot), the engine preempts the lowest-priority DECODING
    # request — its pages spill to a host-side store and the request
    # requeues at the head of its class; restore re-admits through the
    # normal gate (shared prefix pages come back as index hits, the
    # decoded tail scatters back from the spilled payload) and the
    # restored token stream is byte-identical to an un-preempted run.
    # Uniform-priority traffic never preempts (a victim must have STRICTLY
    # lower priority), so the default-on flag is inert for single-class
    # workloads.
    enable_preemption: bool = True
    # optional repro.serving.faults.FaultInjector (paged only): fired at
    # the start of every tick; can exhaust the pool, poison a slot's
    # logits to NaN, storm deadlines, or kill an in-flight prefill.  The
    # chaos harness — None (the default) costs nothing.
    fault_injector: Optional[Any] = None
    # paged layout only: a jax.sharding.Mesh with ("data", "model") axes.
    # When set, the paged pool shards its page axis over data (capacity
    # scales with the data axis at constant per-device memory) and
    # kv_heads over model, per-slot decode inputs shard their slot axis
    # over data, and every device entry point runs with mesh-aware
    # in_shardings/out_shardings — while the block table, BlockAllocator,
    # and the content-hash prefix index stay host-global, so prefix
    # sharing and COW work across shards unchanged.  Non-divisible dims
    # replicate (divisibility guards).  A 1×1 mesh is byte-identical to
    # mesh=None (tests/test_serving.py pins it).  On TPU only data=1 is
    # served: the Pallas kernels run per chip over its KV heads and
    # cannot read pages another chip holds (validate refuses data > 1).
    mesh: Optional[Any] = None
    # paged layout only: self-speculative decoding depth.  k > 0 turns
    # each decode tick into ONE fused draft-k → verify-k device round:
    # every decoding slot drafts k chained tokens (the analog/int8 decode
    # step, K/V written into its reserved pages), then the whole drafted
    # run is re-decoded read-only from the pre-draft state snapshot and
    # accepted up to the first verifier disagreement — which also IS the
    # corrected resample.  Greedy (and per-slot-keyed WTA) streams are
    # byte-identical to speculate_k=0; the win is k tokens per host
    # round-trip instead of one.  A rejected tail rolls pos + recurrent
    # state back through the verifier's per-step states; drafted K/V
    # beyond the rollback point is masked dead rows, overwritten later.
    speculate_k: int = 0
    # paged layout only: bytes cap on the host-side preemption spill
    # store (None = unbounded, the PR-7 behavior).  At the cap the OLDEST
    # records drop first (insertion order — records are only touched
    # again when popped for restore); a dropped record's request
    # re-admits through the normal fresh gate and recomputes its prompt,
    # then teacher-forces its already-published tokens back through the
    # ordinary decode path (deterministic per (key, step), so the
    # recomputed stream is the published one — nothing re-publishes).
    spill_budget_bytes: Optional[int] = None
    # device backend the engine accounts analog events against (see
    # repro.kernels.backend).  "sim" (the default) keeps today's
    # Pallas/jnp math and tallies crossbar/comparator/DAC/rounding event
    # counts per entry-point call, priced into ServingMetrics.analog by
    # the Table I cost model.  Each engine owns a PRIVATE backend
    # instance, so two engines compared side by side never share tallies.
    device_backend: str = "sim"
    # ---- degraded-device serving (see docs/serving.md §"Analog fault
    # model & degraded-mode serving") ----
    # kernels.backend.FaultConfig for device_backend="sim_faulty" — the
    # deterministic stuck-at/drift/read-noise/comparator-offset model.
    # Only valid with the faulty backend (loud otherwise).
    device_fault_config: Optional[Any] = None
    # fire the known-answer canary MAC every N ticks (0 = off); a probe
    # whose relative error vs the host-side answer exceeds
    # canary_threshold counts as a detection event (and triggers tile
    # retirement when tile_retire_threshold > 0)
    canary_interval: int = 0
    canary_threshold: float = 0.05
    # retire (remap-to-spare) crossbar tiles whose stuck-at density
    # crosses this on a canary failure; 0 disables retirement
    tile_retire_threshold: float = 0.0
    # WTA comparator re-reads per sampled token (majority vote); 1 is the
    # plain single-read path, byte-identical to the pre-knob trace
    n_redundant_reads: int = 1
    # logit-sanity detection knobs of the paged decode step: finite but
    # |logit| above the saturation threshold evicts "saturated"; softmax
    # entropy strictly below the floor evicts "entropy_collapse" (0.0
    # disables the entropy check AND keeps the default trace unchanged)
    logit_sat_threshold: float = 1e6
    logit_entropy_floor: float = 0.0
    # graceful-degradation ladder; None disables the policy (detection
    # still evicts, but nothing downshifts)
    degradation: Optional[DegradationPolicy] = None

    def buckets(self) -> tuple[int, ...]:
        if not self.prefill_buckets:
            return tuple(_default_buckets(self.max_len))
        bs = tuple(sorted(set(self.prefill_buckets)))
        if any(b < 1 for b in bs):
            raise ValueError(f"prefill_buckets must be >= 1: {bs}")
        kept = tuple(b for b in bs if b <= self.max_len)
        if not kept:
            raise ValueError(
                f"every prefill bucket in {bs} exceeds max_len="
                f"{self.max_len}; no prompt could ever be admitted"
            )
        return kept

    def max_kv_blocks(self) -> int:
        """Block-table width: blocks covering one request's max_len."""
        return -(-self.max_len // self.kv_block_size)

    def pool_blocks(self, kv_cache_dtype: str = "same") -> int:
        """Total pool pages (incl. the reserved trash page 0).

        ``num_kv_blocks`` is a *memory budget* expressed in native-dtype
        blocks: an int8 pool's pages cost half the K/V bytes, so the same
        budget holds twice the pages (the trash page is counted once) —
        this is how quantization's capacity win reaches ``BlockAllocator``
        admission.  The default (0) is dense-parity capacity, already
        enough for every slot at full ``max_len``, so it is not doubled.
        """
        if self.num_kv_blocks:
            if kv_cache_dtype == "int8":
                return 2 * self.num_kv_blocks - 1
            return self.num_kv_blocks
        return self.max_batch * self.max_kv_blocks() + 1

    def validate(self, kv_cache_dtype: str = "same") -> None:
        """Loud, eager config validation (same spirit as :meth:`buckets`).

        Raises ValueError on an unknown ``kv_cache_dtype`` / ``kv_layout``,
        a non-positive ``kv_block_size``, or a ``num_kv_blocks`` too small
        to ever admit a single request — each of which would otherwise
        surface as an obscure failure deep inside admission or decode.
        """
        if kv_cache_dtype not in ("same", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'same' or 'int8', got "
                f"{kv_cache_dtype!r}"
            )
        if self.kv_layout not in ("paged", "dense"):
            raise ValueError(
                f"kv_layout must be 'paged' or 'dense', got "
                f"{self.kv_layout!r}"
            )
        self.buckets()
        if self.kv_layout == "paged":
            if self.kv_block_size < 1:
                raise ValueError(
                    f"kv_block_size must be >= 1, got {self.kv_block_size}"
                )
            if not isinstance(self.enable_prefix_sharing, bool):
                # a truthy string like "off" would silently ENABLE sharing
                raise ValueError(
                    f"enable_prefix_sharing must be a bool, got "
                    f"{self.enable_prefix_sharing!r}"
                )
            if not isinstance(self.enable_preemption, bool):
                raise ValueError(
                    f"enable_preemption must be a bool, got "
                    f"{self.enable_preemption!r}"
                )
            if self.prefill_chunk < 0:
                raise ValueError(
                    f"prefill_chunk must be >= 0, got {self.prefill_chunk}"
                )
            if self.prefill_chunk and self.prefill_chunk % self.kv_block_size:
                # chunk boundaries must land on block boundaries: chunks
                # scatter whole blocks and the resume grid is block-indexed
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must be a "
                    f"multiple of kv_block_size={self.kv_block_size}"
                )
            # the smallest admissible request: shortest prefill bucket + one
            # generated token, whole lifetime reserved at admission
            need = -(
                -(min(self.buckets()) + 1) // self.kv_block_size
            )
            cap = self.pool_blocks(kv_cache_dtype) - 1  # minus trash page
            if cap < need:
                raise ValueError(
                    f"num_kv_blocks={self.num_kv_blocks} leaves a pool of "
                    f"{cap} allocatable blocks, but even the smallest "
                    f"request (bucket {min(self.buckets())} + 1 token) "
                    f"needs {need}; no request could ever be admitted"
                )
        elif self.prefill_chunk:
            raise ValueError(
                "prefill_chunk is a paged-layout knob; the dense layout "
                "prefills monolithically (it is the byte-identity oracle)"
            )
        if self.fault_injector is not None and self.kv_layout != "paged":
            raise ValueError(
                "fault_injector drives the paged allocator/pipeline; the "
                "dense layout is the fault-free byte-identity oracle"
            )
        if self.mesh is not None:
            if self.kv_layout != "paged":
                raise ValueError(
                    "mesh sharding is a paged-layout knob; the dense "
                    "layout is the single-device byte-identity oracle"
                )
            names = set(getattr(self.mesh, "axis_names", ()))
            if not {"data", "model"} <= names:
                raise ValueError(
                    f"serving mesh needs ('data', 'model') axes, got "
                    f"{sorted(names)}"
                )
            data = dict(
                zip(self.mesh.axis_names, self.mesh.devices.shape)
            )["data"]
            if data > 1 and KOPS.tpu_kernels():
                # the pool's page axis shards over data and any slot may
                # reference any page, but each chip's kernel reads only
                # the pages it holds: cross-chip page reads are not built
                raise ValueError(
                    f"a serving mesh with data={data} > 1 needs paged "
                    "kernels that read pages held by other chips; on TPU "
                    "only the model axis is served (use data=1)"
                )
        if self.speculate_k < 0:
            raise ValueError(
                f"speculate_k must be >= 0, got {self.speculate_k}"
            )
        if self.speculate_k:
            if self.kv_layout != "paged":
                raise ValueError(
                    "speculate_k > 0 drafts through the paged block pool; "
                    "the dense layout is the plain-decode byte-identity "
                    "oracle and cannot speculate"
                )
            if self.speculate_k >= self.max_new_tokens:
                # a draft run at least as long as the whole decode budget
                # can never amortize anything — it would overrun the
                # budget on round one and discard most of its work
                raise ValueError(
                    f"speculate_k={self.speculate_k} must be < the decode "
                    f"budget max_new_tokens={self.max_new_tokens}"
                )
        if self.device_backend not in BK.BACKENDS:
            raise ValueError(
                f"unknown device_backend {self.device_backend!r}; "
                f"registered: {sorted(BK.BACKENDS)}"
            )
        faulty = getattr(
            BK.BACKENDS[self.device_backend], "overrides_compute", False
        )
        if faulty and self.kv_layout != "paged":
            raise ValueError(
                f"device_backend={self.device_backend!r} overrides compute "
                "and needs the paged engine's rebuild/degradation loop; "
                "the dense layout is the healthy byte-identity oracle"
            )
        if self.device_fault_config is not None and not faulty:
            raise ValueError(
                "device_fault_config is only meaningful with a fault "
                f"backend (e.g. 'sim_faulty'); device_backend="
                f"{self.device_backend!r} would silently ignore it"
            )
        if self.n_redundant_reads < 1:
            raise ValueError(
                f"n_redundant_reads must be >= 1, got "
                f"{self.n_redundant_reads}"
            )
        if self.canary_interval < 0:
            raise ValueError(
                f"canary_interval must be >= 0, got {self.canary_interval}"
            )
        if self.canary_threshold <= 0.0:
            raise ValueError(
                f"canary_threshold must be > 0, got {self.canary_threshold}"
            )
        if not 0.0 <= self.tile_retire_threshold <= 1.0:
            raise ValueError(
                f"tile_retire_threshold must be in [0, 1], got "
                f"{self.tile_retire_threshold}"
            )
        if self.degradation is not None:
            pol = self.degradation
            if pol.trip_after < 1 or pol.recover_after < 1:
                raise ValueError(
                    "DegradationPolicy trip_after/recover_after must be "
                    f">= 1, got {pol.trip_after}/{pol.recover_after}"
                )
            if pol.redundant_reads < 1:
                raise ValueError(
                    "DegradationPolicy redundant_reads must be >= 1, got "
                    f"{pol.redundant_reads}"
                )
        if self.spill_budget_bytes is not None:
            if self.kv_layout != "paged":
                raise ValueError(
                    "spill_budget_bytes bounds the paged preemption spill "
                    "store; the dense layout never spills"
                )
            if self.spill_budget_bytes < 0:
                raise ValueError(
                    f"spill_budget_bytes must be >= 0, got "
                    f"{self.spill_budget_bytes}"
                )


@dataclasses.dataclass
class ServingMetrics:
    """Aggregate serving statistics (completed requests only)."""

    completed: int = 0
    total_tokens: int = 0
    wall_time: float = 0.0
    tokens_per_s: float = 0.0
    ttft_mean: float = 0.0      # submit → first generated token, seconds
    decode_steps: int = 0
    prefills: int = 0            # bucket prefills actually COMPUTED
    occupancy_mean: float = 0.0  # mean busy-slot fraction per decode step
    decode_time: float = 0.0     # seconds inside batched decode steps only
    prefix_hits: int = 0         # admissions that skipped prefill entirely
    cow_forks: int = 0           # shared blocks forked on first write
    prefix_partial_hits: int = 0  # admissions that mapped SOME prompt blocks
    prefill_tokens: int = 0       # prefill tokens actually computed
    prefill_tokens_saved: int = 0  # prompt tokens skipped via the index
    ttft_p50: float = 0.0         # TTFT percentiles over completed requests
    ttft_p99: float = 0.0
    admissions: int = 0           # requests seated in a slot (restores too)
    queue_wait_s: float = 0.0     # summed queue → slot wait of admissions
    preemptions: int = 0          # spill-to-host preemptions
    restores: int = 0             # spilled requests re-admitted
    spill_drops: int = 0          # spill records dropped by the bytes budget
    spec_rounds: int = 0          # fused draft+verify rounds dispatched
    spec_drafted: int = 0         # draft tokens considered by acceptance
    spec_accepted: int = 0        # drafted tokens accepted verbatim
    spec_acceptance: float = 0.0  # accepted / drafted
    spec_tokens_per_round: float = 0.0  # tokens emitted per verify call
    # done_reason -> count over every finished request ("eos"/"length" are
    # natural completions; "deadline"/"nan"/"preempted" are evictions)
    evictions: dict = dataclasses.field(default_factory=dict)
    # priority class -> {n, ttft_p50_ms, ttft_p99_ms, latency_p50_ms,
    # latency_p99_ms} — the per-class SLO view (latency = submit → done)
    latency_by_class: dict = dataclasses.field(default_factory=dict)
    # device-backend energy accounting snapshot: analog event tallies,
    # the per-token/per-sample/per-KV-token shape counts they reconcile
    # against, and Table I pricing under RACA vs 1-bit-ADC readout (see
    # DeviceBackend.snapshot).  Empty for the static reference engine.
    analog: dict = dataclasses.field(default_factory=dict)
    # ---- degraded-device serving ----
    degraded_mode: int = 0        # current DegradationPolicy rung (0..3)
    canary_probes: int = 0        # known-answer probes fired
    canary_failures: int = 0      # probes past canary_threshold
    retired_tiles: int = 0        # crossbar tiles remapped to spares
    redundant_read_events: int = 0  # extra comparator re-reads (priced)
    # every ladder transition: {tick, from, to, why} in firing order
    degraded_transitions: list = dataclasses.field(default_factory=list)

    @property
    def decode_step_ms(self) -> float:
        return self.decode_time * 1e3 / max(self.decode_steps, 1)

    def row(self) -> str:
        out = (
            f"tok_per_s={self.tokens_per_s:.1f} "
            f"ttft_ms={self.ttft_mean * 1e3:.1f} "
            f"ttft_p99_ms={self.ttft_p99 * 1e3:.1f} "
            f"step_ms={self.decode_step_ms:.2f} "
            f"occupancy={self.occupancy_mean:.2f}"
        )
        if self.preemptions or self.restores:
            out += f" preempt={self.preemptions} restore={self.restores}"
        if self.spill_drops:
            out += f" spill_drops={self.spill_drops}"
        if self.spec_rounds:
            out += (
                f" spec_acc={self.spec_acceptance:.2f} "
                f"spec_tok_per_round={self.spec_tokens_per_round:.1f}"
            )
        if self.evictions:
            out += " evict=" + ",".join(
                f"{k}:{v}" for k, v in sorted(self.evictions.items())
            )
        if self.degraded_mode or self.degraded_transitions:
            out += (
                f" degraded={self.degraded_mode}"
                f" transitions={len(self.degraded_transitions)}"
            )
        if self.canary_probes:
            out += (
                f" canary={self.canary_failures}/{self.canary_probes}"
            )
        if self.retired_tiles:
            out += f" retired_tiles={self.retired_tiles}"
        if self.redundant_read_events:
            out += f" redundant_reads={self.redundant_read_events}"
        if self.latency_by_class:
            out += " class=" + ",".join(
                f"{k}:n={v['n']}"
                f"/p99={v['latency_p99_ms']:.0f}ms"
                for k, v in sorted(self.latency_by_class.items())
            )
        if self.analog:
            out += (
                f" raca_pj_per_tok="
                f"{self.analog['raca']['energy_pj_per_token']:.0f}"
                f" adc1b_pj_per_tok="
                f"{self.analog['adc1b']['energy_pj_per_token']:.0f}"
            )
        return out


class ServingEngine:
    """Continuous-batching engine over a slot-addressable decode cache."""

    def __init__(self, params, model_cfg: ModelConfig, cfg: ServeConfig):
        if get_model_fns(model_cfg).prefill is None:
            raise ValueError(f"family {model_cfg.family!r} cannot decode")
        if model_cfg.family == "encdec":
            raise ValueError("encdec serving needs frames; token-LM only")
        # validate the whole serving config eagerly, not at admission
        cfg.validate(model_cfg.kv_cache_dtype)
        self.paged = cfg.kv_layout == "paged"
        self.int8 = self.paged and model_cfg.kv_cache_dtype == "int8"
        self.sharing = self.paged and cfg.enable_prefix_sharing
        self.mesh = cfg.mesh if self.paged else None
        self.spec_k = cfg.speculate_k if self.paged else 0
        self.params = params
        self.mcfg = model_cfg
        self.cfg = cfg
        self.sched = Scheduler(cfg.max_batch)
        b = cfg.max_batch
        # private per-engine device backend: analog-event accounting for
        # THIS engine's traffic only.  A compute-overriding backend
        # (sim_faulty) is additionally installed process-wide around each
        # tick (use_backend), so its faulty math reaches the traces; a
        # pure-accounting backend never touches the process dispatch.
        fault_kw = {}
        if cfg.device_fault_config is not None:
            fault_kw["fault"] = cfg.device_fault_config
        self.backend = BK.make_backend(
            cfg.device_backend, model_cfg, **fault_kw
        )
        # base WTA redundant-read factor (R=1 for greedy heads: a digital
        # argmax re-read can never change the vote)
        self._redundant_base = (
            max(int(cfg.n_redundant_reads), 1) if model_cfg.wta_head else 1
        )
        if self.paged:
            self._max_blocks = cfg.max_kv_blocks()
            self.blocks = BlockAllocator(
                cfg.pool_blocks(model_cfg.kv_cache_dtype), n_reserved=1
            )
            # host-authoritative block table; row = trash page 0 when free
            self._table = np.zeros((b, self._max_blocks), np.int32)
            # host mirror of cache["pos"] (drives the decode window width)
            self._host_pos = np.zeros((b,), np.int64)
            self._build_entry_points()
            # rid -> admission plan built by the gate (block hashes,
            # content-derived int8 quant seeds, resume depth, full-hit
            # flag); consumed by _admit_one.  A True gate always leads to
            # admission, so plans cannot leak.
            self._plans: dict[int, dict] = {}
            # rid -> (hashes, seeds): pure function of the prompt, but a
            # back-pressured queue head is re-gated every tick — memoize
            # so only the index lookups rerun per attempt
            self._hash_memo: dict[int, tuple] = {}
            # rid -> in-flight chunked-prefill job, processed FIFO (the
            # ordering that guarantees a sharer's source pages and
            # boundary-state payloads are resident before its first chunk)
            self._jobs: dict[int, dict] = {}
            self._job_fifo: list[int] = []
            # rid -> spill record of a preempted request (host np copies of
            # its pool pages + per-slot leaves + decode counters); consumed
            # by the restore branch of the gate / _admit_one.  Insertion
            # order doubles as the drop order under
            # ``cfg.spill_budget_bytes`` (oldest first — see _store_spill)
            self._spill: dict[int, dict] = {}
            self._spill_bytes = 0
            # recurrent/SSM families can only resume a partial-prefix hit
            # at a chunk boundary whose state snapshot is stashed;
            # attention-only families resume at any matched block
            self._stateful = any(
                k in ("rec", "ssm") for k in model_cfg.layer_pattern
            )
        else:
            self.blocks = None
            self._serve_step = jax.jit(
                SP.make_serve_step(model_cfg), donate_argnums=(1,)
            )
            self._insert = jax.jit(
                SP.make_cache_insert(model_cfg), donate_argnums=(0,)
            )
            self._prefill = jax.jit(self._make_prefill())
        self._base_key = jax.random.PRNGKey(cfg.seed)
        self._cache = None  # allocated lazily on first admission
        self._tokens = np.zeros((b,), np.int32)   # last emitted, per slot
        self._req_keys = np.zeros((b, 2), np.uint32)
        self._steps = np.zeros((b,), np.int32)    # tokens emitted, per slot
        self._injector = cfg.fault_injector if self.paged else None
        # rid -> already-published tokens a recompute-restored request must
        # teacher-force through decode instead of re-recording (set when a
        # spill record is dropped by the bytes budget; always empty for
        # the dense layout)
        self._replay: dict[int, list[int]] = {}
        self._ticks = 0
        self._admissions = 0
        self._queue_wait = 0.0
        self._preemptions = 0
        self._restores = 0
        self._spill_drops = 0
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_emitted = 0
        self._occ_sum = 0.0
        self._decode_steps = 0
        self._prefills = 0
        self._prefix_hits = 0
        self._cow_forks = 0
        self._prefix_partial_hits = 0
        self._prefill_tokens = 0
        self._prefill_tokens_saved = 0
        self._total_tokens = 0
        self._busy_time = 0.0
        self._decode_time = 0.0
        # ---- degraded-device serving state ----
        self._degrade_level = 0
        self._dirty_streak = 0       # consecutive ticks with detections
        self._clean_streak = 0       # consecutive clean canary passes
        self._degraded_transitions: list[dict] = []
        self._canary_probes = 0
        self._canary_failures = 0
        self._tick_dirty = 0         # detection events in the current tick
        self._tick_canary: Optional[bool] = None
        self._canary_expected = (
            KOPS.canary_expected() if cfg.canary_interval else None
        )

    def _build_paged_serve_step(self, n_redundant: int):
        """Jit ONE paged serve-step variant at redundant-read factor R
        (mesh-aware when sharded).  Variants are cached per R — raising R
        under degradation compiles once per (R, window bucket) pair, and
        dropping back reuses the healthy artifact."""
        fn = SP.make_paged_serve_step(
            self.mcfg,
            n_redundant=n_redundant,
            sat_threshold=self.cfg.logit_sat_threshold,
            entropy_floor=self.cfg.logit_entropy_floor,
        )
        if self.mesh is not None:
            sh = self._shardings
            return jax.jit(
                SP.on_kernel_mesh(fn, self.mesh),
                donate_argnums=(1,),
                in_shardings=(
                    sh["params"], sh["cache"], sh["table"],
                    sh["slot_vec"], sh["slot_keys"], sh["slot_vec"],
                ),
                out_shardings=(sh["cache"], sh["slot_vec"], sh["slot_vec"]),
            )
        return jax.jit(fn, donate_argnums=(1,))

    def _get_serve_step(self, n_redundant: int):
        fn = self._serve_steps.get(n_redundant)
        if fn is None:
            fn = self._build_paged_serve_step(n_redundant)
            self._serve_steps[n_redundant] = fn
        return fn

    def _build_entry_points(self) -> None:
        """(Re)build every paged jitted entry point.

        Called at construction, and again whenever the device backend's
        ``fault_version`` bumps: compiled artifacts keep the math they
        were TRACED with, so a drift-bucket crossing, tile retirement, or
        degrade/recover event leaves them computing yesterday's faults —
        the rebuild makes the next call retrace against the backend's
        current state.  Healthy backends never bump, so the recompile
        guards hold unchanged."""
        model_cfg, cfg, b = self.mcfg, self.cfg, self.cfg.max_batch
        base_r = self._redundant_base
        if self.mesh is not None:
            # sharded decode: the SAME four entry points, jitted with
            # mesh-aware in/out shardings (pool pages over data,
            # kv_heads over model, per-slot inputs over data; params
            # replicated).  Donation + static-arg discipline match
            # the unsharded jits, so the recompile guards hold.
            eps = SP.make_sharded_paged_entry_points(
                model_cfg, self.mesh, batch=b,
                n_pages=cfg.pool_blocks(model_cfg.kv_cache_dtype),
                block_size=cfg.kv_block_size,
                speculate_k=self.spec_k,
                n_redundant=base_r,
                sat_threshold=cfg.logit_sat_threshold,
                entropy_floor=cfg.logit_entropy_floor,
            )
            self._serve_step = eps["serve_step"]
            self._suffix_prefill = eps["suffix_prefill"]
            self._state_insert = eps["state_insert"]
            self._page_copy = eps["page_copy"]
            self._page_spill = eps["page_spill"]
            self._page_restore = eps["page_restore"]
            self._state_gather = eps["state_gather"]
            if self.spec_k:
                self._spec_round = eps["spec_round"]
                self._spec_rollback = eps["spec_rollback"]
            self._shardings = eps["shardings"]
            # params live replicated on the mesh — placed ONCE here (a
            # rebuild re-put of already-placed params is a no-op), not
            # re-transferred per call
            self.params = jax.device_put(
                self.params, self._shardings["params"]
            )
        else:
            self._serve_step = self._build_paged_serve_step(base_r)
            # THE paged prefill: a resumable suffix-chunk step (cold
            # prefills run their whole bucket as chunks from zeroed
            # state, partial-prefix hits start at q0 > 0 attending
            # into shared pages).  ``bucket`` is the only static
            # argument — one compile per (bucket, chunk shape) pair;
            # the cache is donated (in-place page writes), the
            # threaded state is NOT (boundary snapshots are stashed
            # in the prefix index and must survive the next chunk
            # call).
            self._suffix_prefill = jax.jit(
                SP.make_paged_suffix_prefill(model_cfg),
                static_argnames=("bucket",), donate_argnums=(1,),
            )
            # prefix-sharing entry points (each compiles at most once
            # — state-leaf shapes are bucket-independent, page ids /
            # logits shapes are fixed): completion/full-hit
            # admissions insert per-slot state leaves, sample the
            # first token from last chunk (or stored) logits, and
            # COW forks copy one pool page onto another
            self._state_insert = jax.jit(
                SP.make_paged_state_insert(model_cfg),
                donate_argnums=(0,),
            )
            self._page_copy = jax.jit(
                SP.make_page_copy(model_cfg), donate_argnums=(0,)
            )
            # preemption entry points (one compile each: page ids ride
            # at the FIXED table width, padded with the trash page):
            # spill gathers a victim's pages for the host-side store
            # (no donation — the cache stays live for the survivors),
            # restore scatters them back at re-admission, and the
            # slot-state gather reads the victim's dense per-slot
            # leaves (pos + recurrent/SSM states)
            self._page_spill = jax.jit(SP.make_page_spill(model_cfg))
            self._page_restore = jax.jit(
                SP.make_page_restore(model_cfg), donate_argnums=(0,)
            )
            self._state_gather = jax.jit(
                SP.make_slot_state_gather(model_cfg)
            )
            if self.spec_k:
                # speculative entry points: the fused draft+verify
                # round (one compile per (window, k) pair — same
                # power-of-two window bucketing as serve_step) and
                # the single-slot rollback (idx + slot traced, ONE
                # compile for the engine's lifetime)
                self._spec_round = jax.jit(
                    SP.make_paged_spec_round(model_cfg, self.spec_k),
                    donate_argnums=(1,),
                )
                self._spec_rollback = jax.jit(
                    SP.make_spec_rollback(model_cfg),
                    donate_argnums=(0,),
                )
        # serve-step variants keyed by redundant-read factor R; the base
        # variant serves healthy traffic, level-2 degradation adds its own
        self._serve_steps = {base_r: self._serve_step}
        self._sample0 = jax.jit(
            lambda logits, key: SP.sample_tokens(
                model_cfg, logits, key[None, :],
                jnp.zeros((1,), jnp.int32),
            )
        )
        # known-answer canary probe through the ACTIVE backend; rebuilt
        # with the rest so it always measures the current fault state.
        # Jitted via a fresh closure: jit's trace cache is keyed on the
        # function object, so jitting the module-level canary_mac
        # directly would keep serving the pre-rebuild trace forever.
        self._canary = jax.jit(lambda key: KOPS.canary_mac(key))
        self._fault_version_seen = getattr(
            self.backend, "fault_version", 0
        )

    def _check_fault_version(self) -> None:
        """Rebuild stale jitted entry points after a backend fault-state
        change (drift bucket, retirement, degrade/recover)."""
        v = getattr(self.backend, "fault_version", None)
        if v is not None and v != self._fault_version_seen:
            self._build_entry_points()

    def _make_prefill(self):
        """Monolithic one-request prefill — the DENSE layout only (the
        paged layout's prefill is the chunked ``_suffix_prefill``, which
        subsumes it; a single whole-bucket chunk is bit-identical)."""
        cfg, max_len = self.mcfg, self.cfg.max_len

        def prefill(params, tokens, key):  # tokens (1, L), key (2,) uint32
            fns = get_model_fns(cfg)
            cache, logits = fns.prefill(
                params, {"tokens": tokens}, cfg, max_len
            )
            tok0 = SP.sample_tokens(
                cfg, logits, key[None, :], jnp.zeros((1,), jnp.int32)
            )
            return cache, tok0, logits

        return prefill

    # -- request API --------------------------------------------------------

    def submit(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: Optional[int] = None,
        priority: int = 1,
        deadline_ms: Optional[float] = None,
    ) -> int:
        """Queue a request; returns its request id.

        ``priority`` is the scheduling class (lower = more urgent;
        ``PRIORITY_INTERACTIVE=0`` overtakes ``PRIORITY_BATCH=1`` at
        admission and may preempt it under pool pressure).  ``deadline_ms``
        is a completion SLO from now: the engine's deadline pass evicts
        the request with reason ``"deadline"`` once it expires, whatever
        state it is in."""
        n = len(prompt_tokens)
        if n == 0:
            # an empty prompt would left-pad to an all-pad window and seed
            # decoding from the logits of a pad token — refuse loudly
            # (same spirit as the max_len check below)
            raise ValueError(
                "empty prompt: at least one prompt token is required "
                "(decoding seeds from the last prompt token's logits)"
            )
        if n > max(self.cfg.buckets()):
            raise ValueError(
                f"prompt length {n} exceeds largest prefill bucket "
                f"{max(self.cfg.buckets())}"
            )
        budget = (
            self.cfg.max_new_tokens if max_new_tokens is None
            else max_new_tokens
        )
        if budget < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {budget}")
        need = self._bucket(n) + budget
        if need > self.cfg.max_len:
            # decode would write past cache capacity (the dynamic-slice
            # write clamps and silently corrupts the last position)
            raise ValueError(
                f"prefill bucket {self._bucket(n)} + {budget} new tokens "
                f"= {need} exceeds cache max_len={self.cfg.max_len}"
            )
        if self.paged:
            nb = self._blocks_needed(self._bucket(n), budget)
            if nb > self.blocks.capacity:
                raise ValueError(
                    f"request needs {nb} KV blocks but the pool only has "
                    f"{self.blocks.capacity}; raise num_kv_blocks"
                )
        req = self.sched.submit(
            prompt_tokens, budget, now=time.perf_counter(),
            priority=priority, deadline_ms=deadline_ms,
        )
        return req.rid

    def _bucket(self, n: int) -> int:
        return next(b for b in self.cfg.buckets() if b >= n)

    def _blocks_needed(self, bucket: int, budget: int) -> int:
        """Whole-lifetime block budget: prefill window + decode tokens.

        Allocated up-front at admission so a decoding request can never hit
        pool exhaustion mid-flight (the paged analogue of the dense
        engine's max_len check in :meth:`submit`)."""
        return -(-(bucket + budget) // self.cfg.kv_block_size)

    def _init_cache(self):
        if self.paged:
            cache = SP.init_paged_decode_cache(
                self.mcfg, self.cfg.max_batch,
                self.cfg.pool_blocks(self.mcfg.kv_cache_dtype),
                self.cfg.kv_block_size,
            )
            if self.mesh is not None:
                # place the pool sharded from the start: pages over data,
                # kv_heads over model — each device holds 1/|data| of the
                # pool, which is where capacity scaling comes from
                cache = jax.device_put(cache, self._shardings["cache"])
            return cache
        return SP.init_decode_cache(
            self.mcfg, self.cfg.max_batch, self.cfg.max_len
        )

    def _put(self, x, kind: str):
        """Host→device transfer for a per-tick decode input.

        Unsharded engines take the plain ``jnp.asarray`` path; under a
        mesh the transfer is PLACED (``jax.device_put`` with the entry
        point's NamedSharding) so the jit never needs a follow-up
        reshard of an uncommitted array."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._shardings[kind])

    def _put_tree(self, tree, kind: str):
        """Like :meth:`_put` for a pytree (spill payloads, state leaves)."""
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, tree)
        return jax.device_put(tree, self._shardings[kind])

    def _chunk_tokens(self, bucket: int) -> int:
        """The prefill chunk grid for ``bucket`` (0 → whole bucket)."""
        return min(self.cfg.prefill_chunk or bucket, bucket)

    def _resume_tokens(self, n_matched: int, bucket: int) -> int:
        """How many prompt tokens a partial hit can SKIP computing.

        Attention-only families resume at the full matched depth: suffix
        hidden states are per-position functions of (token, attended
        K/V), so any block boundary is an exact resume point.
        Recurrent/SSM families additionally need the carried state at the
        resume point, which the chunked prefill stashes at CHUNK
        boundaries only — so the matched depth truncates down to the
        chunk grid (every registrant shares the grid, so a grid-boundary
        block always carries a state snapshot by the time this request's
        first chunk runs — FIFO job order).
        """
        p = n_matched * self.cfg.kv_block_size
        if not self._stateful:
            return p
        grid = self._chunk_tokens(bucket)
        return (p // grid) * grid

    def _try_reserve_blocks(self, req: Request) -> bool:
        """Admission gate: reserve the request's whole block budget, or
        refuse.  Reserving *inside* the gate (not later in the prefill) is
        what makes multi-admission ticks safe: each True answer has already
        taken its pages, so the next queue head is gated against what is
        actually left.  A True from the gate always leads to admission and
        a False leaves the allocator COMPLETELY untouched — matching is a
        read-only probe (``longest_prefix_match``) and the refcount bumps
        for the mapped pages happen only inside the atomic ``reserve``, so
        a refused or re-gated request can never leak a reference
        (tests/test_serving.py::test_admission_gate_refusal_has_no_side_effects).

        With prefix sharing the gate maps the deepest resident chain hit
        into the request's table (refcount bump — capacity win even when
        the compute resume point truncates below it), reserves one spare
        COW page for a full hit ending in a partial boundary block (the
        request WILL write there at its first decode token), and registers
        the request's own fresh prompt blocks immediately so same-tick
        duplicates already share; their CONTENT lands later, chunk by
        chunk, which is safe because prefill jobs run FIFO — a sharer's
        first chunk never precedes its source's covering chunk.
        """
        bucket = self._bucket(len(req.prompt))
        nb_total = self._blocks_needed(bucket, req.max_new_tokens)
        bs = self.cfg.kv_block_size
        n_prompt = -(-bucket // bs)
        plan: dict = {
            "full_hit": False, "hashes": None, "seeds": None,
            "n_prompt": n_prompt, "n_shared": 0, "resume": 0,
            "bucket": bucket,
        }
        if self.sharing or self.int8:
            memo = self._hash_memo.get(req.rid)
            if memo is None:
                hashes = prefix_block_hashes(
                    left_pad(req.prompt, bucket), bs
                )
                # canonical int8 rounding seeds: content-derived per
                # block, so identical prefixes re-quantize to
                # bit-identical codes
                memo = (
                    hashes,
                    np.asarray([s for _, s in hashes], np.uint32),
                )
                self._hash_memo[req.rid] = memo
            plan["hashes"], plan["seeds"] = memo
        rec = self._spill.get(req.rid)
        if rec is not None:
            return self._gate_restore(req, plan, rec, nb_total)
        shared: list[int] = []
        if self.sharing:
            shared = self.blocks.longest_prefix_match(
                [h for h, _ in plan["hashes"]]
            )
        full = len(shared) == n_prompt
        # a shared partial boundary block is written at the first decode
        # token — reserve its fork page NOW so the COW can never starve
        n_spare = 1 if (full and bucket % bs != 0) else 0
        n_new = nb_total - len(shared)
        if not self.blocks.can_alloc(n_new + n_spare):
            return False
        pages = self.blocks.reserve(req.rid, n_new, shared, n_spare)
        if self.sharing:
            for i in range(len(shared), n_prompt):
                self.blocks.register(pages[i], plan["hashes"][i][0])
            plan["full_hit"] = full
            plan["n_shared"] = len(shared)
            if not full:
                plan["resume"] = self._resume_tokens(len(shared), bucket)
        self._plans[req.rid] = plan
        return True

    def _gate_restore(
        self, req: Request, plan: dict, rec: dict, nb_total: int
    ) -> bool:
        """Admission gate for a preempted (spilled) request.

        Same atomic shape as the fresh-admission gate, with two twists.
        First, the prefix probe is truncated to the request's PRISTINE
        prompt blocks: once a decode step has written into an unaligned
        boundary block (``rec["dirty"]``), that block's content diverged
        from its chain hash — taking a pristine index hit there would
        silently drop the decoded rows, so the spilled copy must come back
        instead.  Second, fresh pristine prompt blocks re-register under
        their hashes (guarded: an identical prompt may have re-registered
        them while this request sat spilled), so a restored request is a
        first-class sharing citizen again.
        """
        bucket, bs = plan["bucket"], self.cfg.kv_block_size
        n_prompt = plan["n_prompt"]
        n_clean = n_prompt - 1 if rec["dirty"] else n_prompt
        shared: list[int] = []
        if self.sharing:
            shared = self.blocks.longest_prefix_match(
                [h for h, _ in plan["hashes"]][:n_clean]
            )
        # an undirtied full match of an unaligned prompt WILL write its
        # shared boundary block at the first decode step — same COW spare
        # rule as a fresh full-hit admission
        n_spare = 1 if (
            len(shared) == n_prompt and bucket % bs != 0
        ) else 0
        n_new = nb_total - len(shared)
        if not self.blocks.can_alloc(n_new + n_spare):
            return False
        pages = self.blocks.reserve(req.rid, n_new, shared, n_spare)
        if self.sharing:
            for i in range(len(shared), n_clean):
                if self.blocks.lookup(plan["hashes"][i][0]) is None:
                    self.blocks.register(pages[i], plan["hashes"][i][0])
            plan["n_shared"] = len(shared)
        plan["restore"] = True
        self._plans[req.rid] = plan
        return True

    def _release_if_done(self, req: Request) -> None:
        """Reclaim an evicted request's KV blocks and neutralize its slot.

        The request's page references (mapped + any unspent COW spare) are
        released; pages reach the free list only at refcount zero — a
        prefix block still shared by another live request survives, and
        its index entry with it.  The slot's table row is pointed at the
        trash page so the still-running batched decode step writes nowhere
        a live request reads — this is how a mid-flight refill recycles
        memory."""
        if not (self.paged and req.state is RequestState.DONE):
            return
        self.blocks.free(req.rid)
        # eviction nulled req.slot (the slot is no longer this request's
        # — the next admission reuses it); the historical binding lives
        # in req.done_slot, which is the row to neutralize here
        self._table[req.done_slot, :] = 0

    def _admit_one(self, req: Request) -> None:
        """Bind an admitted request to its slot.

        Dense: monolithic prefill + slot insert, decode starts immediately
        (the PR-1 oracle path, unchanged).  Paged: enqueue a chunked
        prefill job — the slot's table row stays pointed at the trash page
        and its per-slot cache leaves stay engine-owned (threaded through
        the chunk steps host-side) until the job completes, so the batched
        decode steps running for the OTHER slots in the meantime can never
        corrupt a prefill in flight."""
        self._admissions += 1
        self._queue_wait += time.perf_counter() - req.queued_since
        slot = req.slot
        plen = self._bucket(len(req.prompt))
        rkey = jax.random.fold_in(self._base_key, req.rid)
        if self._cache is None:
            self._cache = self._init_cache()
        with jax.profiler.TraceAnnotation("engine.admit.wait"):
            self._req_keys[slot] = np.asarray(rkey)
        if not self.paged:
            toks = np.asarray([left_pad(req.prompt, plen)], np.int32)
            one_cache, tok0, _ = self._prefill(
                self.params, jnp.asarray(toks), rkey
            )
            self._cache = self._insert(self._cache, one_cache, slot)
            self._prefills += 1
            self._prefill_tokens += plen
            # monolithic dense prefill forwards the whole padded bucket
            # and samples the first token in the same call
            self.backend.note_call(
                SP.analog_call_profile("suffix_prefill", tokens=plen)
            )
            self.backend.note_call(SP.analog_call_profile("sample0"))
            self._finish_admission(req, tok0)
            return
        plan = self._plans.pop(req.rid)
        self._hash_memo.pop(req.rid, None)
        if plan.get("restore"):
            self._restore_one(req, plan)
            return
        pages = self.blocks.owned(req.rid)  # reserved by the gate
        row = np.zeros((self._max_blocks,), np.int32)
        row[: len(pages)] = pages
        if plan["full_hit"]:
            # stash the terminal payload NOW if it already exists: the
            # registrant may in-place-diverge its partial boundary block
            # (dropping the index entry and payload with it) before this
            # job reaches the head of the prefill FIFO.  A logits-less
            # (None, state) payload is a CHUNK-BOUNDARY snapshot of a
            # longer in-flight prompt whose grid boundary happens to be
            # this prompt's terminal hash — not a terminal payload; the
            # job will demote to a suffix recompute instead.
            payload = self.blocks.payload(plan["hashes"][-1][0])
            plan["payload"] = (
                payload
                if payload is not None and payload[0] is not None
                else None
            )
        elif plan["n_shared"] > 0:
            self._prefix_partial_hits += 1
            self._prefill_tokens_saved += plan["resume"]
        self._jobs[req.rid] = {
            "req": req,
            "row": row,
            "plan": plan,
            "q0": plen if plan["full_hit"] else plan["resume"],
            "bucket": plen,
            "rkey": rkey,
            "state": None,
            "tokens": left_pad(req.prompt, plen),
        }
        self._job_fifo.append(req.rid)

    def _restore_one(self, req: Request, plan: dict) -> None:
        """Re-bind a spilled request to its new slot, byte-exactly.

        Shared prefix pages came back as index hits through the gate; the
        rest of the request's USED pages (suffix prompt blocks, the dirty
        boundary block, decoded tail blocks) scatter back from the spilled
        payload — positions the request never reached point at the trash
        page, so one fixed-width restore compile serves every shape.  The
        per-slot leaves (``pos`` + recurrent/SSM state), last token, step
        counter, and per-request PRNG key are restored verbatim, which is
        what makes the remaining token stream byte-identical to an
        un-preempted run (the key is ``fold_in(base, rid)`` — a pure
        function of the rid — and WTA noise is a function of (key, step)).
        No token is recorded here: the request resumes mid-stream.
        """
        rec = self._pop_spill(req.rid)
        slot = req.slot
        pages = self.blocks.owned(req.rid)
        row = np.zeros((self._max_blocks,), np.int32)
        row[: len(pages)] = pages
        ids = np.zeros((self._max_blocks,), np.int32)
        n_shared = plan["n_shared"]
        ids[n_shared : rec["n_used"]] = row[n_shared : rec["n_used"]]
        self._cache = self._page_restore(
            self._cache,
            self._put(ids, "replicated"),
            self._put_tree(rec["pages"], "replicated"),
        )
        self._cache = self._state_insert(
            self._cache,
            self._put_tree(rec["state"], "replicated"),
            slot,
        )
        self._table[slot] = row
        self._host_pos[slot] = rec["pos"]
        self._tokens[slot] = rec["token"]
        self._steps[slot] = rec["steps"]
        self.sched.start_decode(req)
        self._restores += 1

    def _finish_admission(self, req: Request, tok0) -> None:
        """Shared admission tail: first token, decode start, bookkeeping."""
        slot = req.slot
        self.sched.start_decode(req)
        rep = self._replay.get(req.rid)
        if rep:
            # recompute-restore of a dropped spill record: the first
            # `len(rep)` tokens were already published before the
            # preemption — seed decode with the recorded first token
            # (bitwise what `tok0` just resampled) and teacher-force the
            # rest through the ordinary ticks; nothing re-records
            self._tokens[slot] = rep.pop(0)
            if not rep:
                del self._replay[req.rid]
            self._steps[slot] = 1
            return
        with jax.profiler.TraceAnnotation("engine.prefill.wait"):
            t0 = int(tok0[0])  # blocks on the prefill — TTFT stamps after it
        self._tokens[slot] = t0
        self._steps[slot] = 1
        self._total_tokens += 1
        self.sched.record_token(
            req, t0, self.cfg.eos_token, time.perf_counter()
        )
        self._release_if_done(req)  # budget=1 or instant EOS

    def _complete_job(self, rid: int, job: dict, tok0) -> None:
        """Finish a chunked-prefill job: publish the real block-table row
        (decode writes may now land in the request's own pages), mirror
        the final position, and start decoding."""
        req = job["req"]
        self._table[req.slot] = job["row"]
        self._host_pos[req.slot] = job["bucket"]
        self._job_fifo.pop(0)
        del self._jobs[rid]
        self._finish_admission(req, tok0)

    def _resume_state(self, plan: dict, q0: int) -> dict:
        """State leaves entering a job's first computed chunk: zeroed for
        a cold start, the stashed boundary snapshot for a stateful
        partial-prefix resume (attention-only families carry no recurrent
        state — their resume needs only the shared pages)."""
        if q0 == 0 or not self._stateful:
            return SP.init_prefill_state(self.mcfg)
        h = plan["hashes"][q0 // self.cfg.kv_block_size - 1][0]
        payload = self.blocks.payload(h)
        assert payload is not None, (
            "missing boundary-state snapshot for a grid-aligned resume"
        )
        return payload[1]

    # -- preemption / eviction ----------------------------------------------

    @staticmethod
    def _spill_nbytes(rec: dict) -> int:
        """Host bytes a spill record pins: its page payload + state leaves
        (the np arrays — counters and ints are noise).  The bytes-based KV
        cost framing: a record's weight is what its K/V actually costs at
        the pool dtype, so int8 records charge half the budget of bf16
        ones for the same token count."""
        return int(sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((rec["pages"], rec["state"]))
        ))

    def _pop_spill(self, rid: int) -> Optional[dict]:
        """Remove a spill record (restore / cancel), keeping the bytes
        accounting exact.  Returns the record, or None if it was never
        stored — or already dropped by the budget."""
        rec = self._spill.pop(rid, None)
        if rec is not None:
            self._spill_bytes -= self._spill_nbytes(rec)
        return rec

    def _store_spill(self, rid: int, rec: dict) -> None:
        """Insert a spill record, then enforce ``spill_budget_bytes``.

        Over the cap, the OLDEST records drop first (dict insertion order
        — a record is only ever touched again when popped for restore, so
        age IS recency).  The just-inserted record is eligible too: a
        single record larger than the whole budget drops immediately.  A
        dropped record's request stays queued and re-admits through the
        normal fresh gate — full grid-aligned prompt recompute through
        the chunked prefill (prefix hits still apply) — and its
        already-published tokens move to ``_replay``: decode teacher-
        forces them back, re-deriving the decoded tail's K/V bit-for-bit
        without re-publishing anything (greedy/WTA sampling is a pure
        function of (key, step), so the recomputed tokens ARE the
        published ones).
        """
        self._spill[rid] = rec
        self._spill_bytes += self._spill_nbytes(rec)
        budget = self.cfg.spill_budget_bytes
        if budget is None:
            return
        while self._spill and self._spill_bytes > budget:
            old_rid = next(iter(self._spill))
            old = self._pop_spill(old_rid)
            self._replay[old_rid] = list(old["replay"])
            self._spill_drops += 1

    def _preempt(self, req: Request) -> None:
        """Spill a DECODING request to the host-side store and requeue it.

        The victim's USED pages (``ceil(pos / block_size)`` of them, padded
        with the trash page to the fixed table width — one spill compile
        ever) gather to host memory together with its per-slot leaves and
        decode counters; then its whole reservation is released — shared
        prefix pages survive for their other owners, private pages hit the
        free list immediately, which is the capacity the preempting
        request is about to take.  The scheduler requeues the victim at
        the head of its priority class.
        """
        slot, rid = req.slot, req.rid
        pages = self.blocks.owned(rid)
        pos = int(self._host_pos[slot])
        bs = self.cfg.kv_block_size
        bucket = self._bucket(len(req.prompt))
        n_used = -(-pos // bs)
        ids = np.zeros((self._max_blocks,), np.int32)
        ids[:n_used] = pages[:n_used]
        payload = jax.tree.map(
            np.asarray,
            self._page_spill(self._cache, self._put(ids, "replicated")),
        )
        state = jax.tree.map(
            np.asarray,
            self._state_gather(self._cache, slot),
        )
        self._store_spill(rid, {
            "bucket": bucket,
            "n_used": n_used,
            "pos": pos,
            # once a decode step wrote into an unaligned boundary prompt
            # block, its content diverged from the chain hash — the
            # restore gate must NOT take a pristine index hit there
            "dirty": bucket % bs != 0 and pos > bucket,
            "pages": payload,
            "state": state,
            "token": int(self._tokens[slot]),
            "steps": int(self._steps[slot]),
            # published so far — the replay list if this record is later
            # dropped by the bytes budget (frozen: a queued request
            # publishes nothing until it decodes again)
            "replay": list(req.output),
        })
        self.blocks.free(rid)
        self._table[slot, :] = 0
        self.sched.requeue(req, now=time.perf_counter())
        self._preemptions += 1

    def _preempt_pass(self) -> None:
        """Evict lowest-priority decoders until the queue head admits.

        Runs after normal admission: while the most-urgent queued request
        outranks some DECODING request (strictly — uniform-priority
        traffic never preempts), spill the weakest victim (lowest class,
        then newest) and retry admission.  Each round shrinks the active
        set by one, so the loop is bounded by ``max_batch``; it stops as
        soon as the head stops outranking the floor — either because it
        was admitted or because only its own class (or better) remains
        live.
        """
        while True:
            head = self.sched.peek()
            if head is None:
                return
            victims = [
                r for r in self.sched.active()
                if r.priority > head.priority
            ]
            if not victims:
                return
            victim = max(victims, key=lambda r: (r.priority, r.rid))
            self._preempt(victim)
            for req in self.sched.admit(self._try_reserve_blocks):
                with jax.profiler.TraceAnnotation(
                    "engine.admit.request", rid=req.rid
                ):
                    self._admit_one(req)

    def _evict_request(self, req: Request, reason: str, now: float) -> None:
        """Terminally evict a request in ANY live state, atomically.

        QUEUED requests cancel off the queue (dropping any spill record —
        an expired preempted request never comes back); PREFILL requests
        drop their pipeline job and free every reserved page
        (:meth:`_kill_job`); DECODING requests release through the normal
        eviction path.  Every path stamps the typed ``done_reason``.

        Logit-sanity evictions also count as detection events for the
        degradation policy's per-tick pressure signal.
        """
        if reason in SP.SANITY_REASONS.values():
            self._tick_dirty += 1
        if req.state is RequestState.QUEUED:
            self.sched.cancel(req, reason, now)
            if self.paged:
                self._hash_memo.pop(req.rid, None)
                self._pop_spill(req.rid)
                self._replay.pop(req.rid, None)
        elif req.state is RequestState.PREFILL:
            if self.paged:
                self._kill_job(req)
            self.sched.evict(req, reason, now)
            if self.paged:
                self._table[req.done_slot, :] = 0
        elif req.state is RequestState.DECODE:
            self.sched.evict(req, reason, now)
            self._release_if_done(req)

    def _kill_job(self, req: Request) -> None:
        """Drop an in-flight chunked-prefill job and free its pages.

        The dead job's registered-but-not-fully-written prompt blocks are
        deregistered BEFORE the free (their content never finished
        landing; leaving them indexed would hand garbage to later
        admissions).  Any such page still alive through a sharer's
        reservation is *garbage with a believer*: jobs queued behind this
        one mapped it at their gate assuming FIFO order would fill it —
        each is demoted to recompute from before its first garbage block
        (:meth:`_demote_job_for_garbage`).  Jobs AHEAD in the FIFO cannot
        reference these pages (they were registered at this job's gate,
        after theirs), and no DECODING request can either (completion is
        FIFO too), so the cascade over queued jobs is exhaustive.
        """
        rid = req.rid
        job = self._jobs.pop(rid)
        self._job_fifo.remove(rid)
        plan = job["plan"]
        garbage: set[int] = set()
        if self.sharing:
            bs = self.cfg.kv_block_size
            for i in range(plan["n_shared"], plan["n_prompt"]):
                if job["q0"] < min((i + 1) * bs, job["bucket"]):
                    page = int(job["row"][i])
                    self.blocks.deregister(page)
                    garbage.add(page)
        self.blocks.free(rid)
        garbage = {p for p in garbage if self.blocks.refcount(p) > 0}
        for orid in self._job_fifo:
            self._demote_job_for_garbage(self._jobs[orid], garbage)

    def _demote_job_for_garbage(self, job: dict, garbage: set) -> None:
        """Lower a queued job's resume point below its first garbage page.

        ``garbage`` pages are mapped in ``job["row"]`` but their promised
        content died with the killed writer.  Everything BELOW the first
        garbage block is still valid (written, or registered by a live
        owner); the job recomputes from there — rewriting the garbage
        pages itself, with exactly the bits the dead writer would have
        produced (content-derived int8 seeds keep even quantized blocks
        bit-identical across writers).  Only the FIFO head ever advances
        ``q0``, so a demoted job has not computed anything yet and its
        threaded state is still unset; stateful families additionally
        walk down the chunk grid to the deepest boundary whose state
        snapshot is still stashed.
        """
        if not garbage:
            return
        plan = job["plan"]
        bs = self.cfg.kv_block_size
        frontier = min(-(-job["q0"] // bs), plan["n_prompt"])
        bad = next(
            (
                i for i in range(frontier)
                if int(job["row"][i]) in garbage
            ),
            None,
        )
        if bad is None:
            return
        q0 = bad * bs
        if self._stateful:
            grid = self._chunk_tokens(job["bucket"])
            q0 = (q0 // grid) * grid
            while q0 > 0 and self.blocks.payload(
                plan["hashes"][q0 // bs - 1][0]
            ) is None:
                q0 -= grid
        plan["full_hit"] = False
        job["q0"] = q0
        job["state"] = None

    def _nan_payload(self) -> dict:
        """A cached all-non-finite page payload for the NaN injector.

        Float pool leaves (K/V or their scale planes) get a NaN row at
        payload index 0 ONLY — an int8 pool's dequant is ``code * NaN
        scale``, so the poison propagates at any pool dtype.  The other
        rows stay zero: the scatter's fixed-width ids pad with the trash
        page, and NaN-ing the trash page would non-finite EVERY slot
        (masked attention weights are exactly 0, but 0·NaN = NaN on the
        V side).  Shapes match the spill payload, so scattering reuses
        the one restore compile.
        """
        if getattr(self, "_nan_rows", None) is None:
            self._nan_rows = {}
            for name in SP.PAGE_POOL_LEAVES:
                if name in self._cache:
                    leaf = self._cache[name]
                    shape = list(leaf.shape)
                    shape[2] = self._max_blocks
                    dt = np.dtype(leaf.dtype)
                    rows = np.zeros(shape, dt)
                    # jnp.issubdtype, not np: bfloat16 is an ml_dtypes
                    # extension type that numpy does not class as floating
                    if jnp.issubdtype(dt, jnp.floating):
                        rows[:, :, 0] = np.nan
                    self._nan_rows[name] = rows
        return self._nan_rows

    def _poison_nan(self, req: Request) -> bool:
        """Overwrite one of ``req``'s PRIVATE read-window pages with NaNs.

        The injected analog-garbage fault: the next decode step reads the
        poisoned block, its logits go non-finite, and the engine's ok-flag
        guard evicts the request with reason ``"nan"``.  Only a
        refcount-1 page may be poisoned (corrupting a shared page would
        take innocent requests down with it); the page is deregistered
        first, exactly as a real content divergence would be.  Returns
        False if the request has no private page in its read window yet
        (a fresh full-hit admission) — the injector then tries another
        victim.
        """
        slot, rid = req.slot, req.rid
        pages = self.blocks.owned(rid)
        n_read = max(1, -(-int(self._host_pos[slot]) // self.cfg.kv_block_size))
        target = next(
            (
                i for i in reversed(range(min(n_read, len(pages))))
                if self.blocks.refcount(pages[i]) == 1
            ),
            None,
        )
        if target is None:
            return False
        self.blocks.deregister(pages[target])
        # payload row 0 is the NaN row (see _nan_payload); the rest of the
        # fixed-width vector scatters harmless zeros into the trash page
        ids = np.zeros((self._max_blocks,), np.int32)
        ids[0] = pages[target]
        self._cache = self._page_restore(
            self._cache,
            self._put(ids, "replicated"),
            self._put_tree(self._nan_payload(), "replicated"),
        )
        return True

    def _prefill_tick(self, emitted: list[tuple[int, int]]) -> None:
        """Advance the chunked-prefill pipeline by at most one compute
        chunk (≤ ``prefill_chunk`` tokens), completing any number of
        zero-compute full hits along the way.

        Jobs run strictly FIFO — the ordering that makes gate-time
        registration safe: by the time a sharer's first chunk (or a full
        hit's payload fetch) runs, the source request's covering chunks
        have already written their pages and boundary snapshots."""
        computed = False
        while self._job_fifo:
            rid = self._job_fifo[0]
            job = self._jobs[rid]
            req, plan = job["req"], job["plan"]
            bucket = job["bucket"]
            if plan["full_hit"]:
                payload = plan.get("payload") or self.blocks.payload(
                    plan["hashes"][-1][0]
                )
                # a logits-less boundary snapshot cannot seed the first
                # token — only a completed identical prompt's terminal
                # (logits, state) can; anything else demotes below (the
                # recompute republishes terminal logits on the hash, so
                # LATER repeats of this prompt full-hit properly)
                if payload is not None and payload[0] is not None:
                    logits, state = payload
                    self._cache = self._state_insert(
                        self._cache, state, req.slot
                    )
                    tok0 = self._sample0(logits, job["rkey"])
                    self.backend.note_call(
                        SP.analog_call_profile("sample0")
                    )
                    self._prefix_hits += 1
                    self._prefill_tokens_saved += bucket
                    self._complete_job(rid, job, tok0)
                    emitted.append((rid, req.output[-1]))
                    continue
                # no usable terminal payload: it died while this job
                # waited (the registrant in-place-diverged its boundary
                # block with its decode writes), or the matched terminal
                # hash only ever carried a longer prompt's chunk-boundary
                # snapshot.  Demote to a minimal grid-aligned suffix
                # recompute — the interior shared pages are still
                # content-valid, only the boundary block and the
                # (logits, state) must be regenerated
                plan["full_hit"] = False
                grid = (
                    self._chunk_tokens(bucket) if self._stateful
                    else self.cfg.kv_block_size
                )
                job["q0"] = ((bucket - 1) // grid) * grid
                bs = self.cfg.kv_block_size
                last = plan["n_prompt"] - 1
                page = int(job["row"][last])
                if (
                    bucket % bs != 0
                    and self.blocks.refcount(page) > 1
                    and self.blocks.spare_count(rid) > 0
                ):
                    # the diverged boundary page now carries the
                    # registrant's live decode rows — the recompute must
                    # NOT rewrite it in place.  Fork onto the spare the
                    # full-hit plan reserved; no device copy is needed
                    # because the recompute rewrites every row of the
                    # block (prompt rows with identical bits, the rest
                    # with masked zero padding).
                    _, new = self.blocks.cow_fork(rid, last)
                    job["row"][last] = new
                    self._cow_forks += 1
                self._prefix_partial_hits += 1
                self._prefill_tokens_saved += job["q0"]
            if computed:
                break
            q0 = job["q0"]
            if job["state"] is None:
                job["state"] = self._resume_state(plan, q0)
            grid = self._chunk_tokens(bucket)
            c = min((q0 // grid + 1) * grid, bucket) - q0
            bs = self.cfg.kv_block_size
            b0, b1 = q0 // bs, -(-(q0 + c) // bs)
            with jax.profiler.TraceAnnotation(
                "engine.prefill.chunk", rid=rid
            ):
                args = [
                    self.params,
                    self._cache,
                    job["state"],
                    jnp.asarray([job["tokens"][q0 : q0 + c]], jnp.int32),
                    jnp.asarray(job["row"][: plan["n_prompt"]]),
                    jnp.asarray(q0, jnp.int32),
                ]
                if self.int8:
                    args.append(jnp.asarray(plan["seeds"][b0:b1]))
                self._cache, job["state"], logits = self._suffix_prefill(
                    *args, bucket=bucket
                )
            self._prefill_tokens += c
            self.backend.note_call(
                SP.analog_call_profile("suffix_prefill", tokens=c)
            )
            job["q0"] = q0 + c
            computed = True
            done = job["q0"] == bucket
            if self.sharing:
                # stash the boundary snapshot on the chunk's last block so
                # later admissions can resume (or, on the final chunk with
                # its logits, skip) exactly here; if an in-flight
                # duplicate registered the hash first, its own chunk
                # attaches — ours would be identical bits anyway
                h_last = plan["hashes"][b1 - 1][0]
                if self.blocks.lookup(h_last) == int(job["row"][b1 - 1]):
                    self.blocks.set_payload(
                        h_last, (logits if done else None, job["state"])
                    )
            if not done:
                break
            self._cache = self._state_insert(
                self._cache, job["state"], req.slot
            )
            tok0 = self._sample0(logits, job["rkey"])
            self.backend.note_call(SP.analog_call_profile("sample0"))
            self._prefills += 1
            self._complete_job(rid, job, tok0)
            emitted.append((rid, req.output[-1]))

    def tick(self) -> list[tuple[int, int]]:
        """One engine iteration: admit, advance the (chunked) prefill
        pipeline, then one batched decode step for the decoding slots.

        A compute-overriding backend (sim_faulty) is installed
        process-wide for the duration of the tick (exception-safe), so
        any trace this tick causes picks up its faulty math; the
        degradation policy updates once per tick, after detections and
        the canary have spoken.

        Returns the (rid, token) pairs emitted during this tick.
        """
        ctx = (
            BK.use_backend(self.backend)
            if getattr(self.backend, "overrides_compute", False)
            else contextlib.nullcontext()
        )
        with ctx, jax.profiler.TraceAnnotation("engine.tick"):
            self._tick_dirty = 0
            self._tick_canary = None
            try:
                return self._tick_inner()
            finally:
                self._policy_update()

    def _tick_inner(self) -> list[tuple[int, int]]:
        t_start = time.perf_counter()
        emitted: list[tuple[int, int]] = []
        if self._injector is not None:
            self._injector.fire(self, self._ticks)
        self._ticks += 1
        if self.paged:
            self._fault_pass()
        # deadline pass: expired requests evict in whatever state they
        # are — queued, mid-chunked-prefill (job + pages dropped
        # atomically), or decoding
        with jax.profiler.TraceAnnotation("engine.admit"):
            expired = self.sched.expired(time.perf_counter())
            for req in expired:
                self._evict_request(req, "deadline", time.perf_counter())
            gate = self._try_reserve_blocks if self.paged else None
            pol = self.cfg.degradation
            shed = (
                pol.shed_priority_above
                if pol is not None and self._degrade_level >= 3
                else None
            )
            for req in self.sched.admit(gate, shed_priority_above=shed):
                with jax.profiler.TraceAnnotation(
                    "engine.admit.request", rid=req.rid
                ):
                    self._admit_one(req)
                if not self.paged:
                    emitted.append((req.rid, req.output[-1]))
        if self.paged and self.cfg.enable_preemption:
            with jax.profiler.TraceAnnotation("engine.preempt"):
                self._preempt_pass()
        if self.paged:
            with jax.profiler.TraceAnnotation("engine.prefill"):
                self._prefill_tick(emitted)
        active = self.sched.active()
        # speculate only when every draft write stays inside max_len —
        # near-capacity tails fall back to plain single-token ticks, so
        # an overrun can never clamp into a slot's live final block —
        # and only below degradation level 1 (a k-deep draft multiplies
        # one bad logit row's blast radius by k)
        spec_now = (
            bool(active) and self.spec_k > 0 and self._spec_viable(active)
            and self._degrade_level < 1
        )
        if active and self.sharing:
            with jax.profiler.TraceAnnotation("engine.cow"):
                self._cow_pass(active, self.spec_k if spec_now else 1)
        if active:
            t_dec = time.perf_counter()
            if spec_now:
                with jax.profiler.TraceAnnotation("engine.spec"):
                    self._spec_tick(active, emitted)
                self._decode_time += time.perf_counter() - t_dec
                self._busy_time += time.perf_counter() - t_start
                return emitted
            sane_np = None
            with jax.profiler.TraceAnnotation("engine.decode.dispatch"):
                if self.paged:
                    w = self._window_blocks(active)
                    r_eff = self._redundant_effective()
                    self._cache, nxt, sane = self._get_serve_step(r_eff)(
                        self.params,
                        self._cache,
                        self._put(self._table[:, :w], "table"),
                        self._put(self._tokens, "slot_vec"),
                        self._put(self._req_keys, "slot_keys"),
                        self._put(self._steps, "slot_vec"),
                    )
                else:
                    r_eff = 1
                    self._cache, nxt = self._serve_step(
                        self.params,
                        self._cache,
                        jnp.asarray(self._tokens),
                        jnp.asarray(self._req_keys),
                        jnp.asarray(self._steps),
                    )
            with jax.profiler.TraceAnnotation("engine.decode.wait"):
                if self.paged:
                    sane_np = np.asarray(sane)
                nxt_np = np.asarray(nxt)  # device sync — decode_time is honest
            if self.paged:
                self._host_pos += 1  # mirrors the step's pos+1, every slot
            with jax.profiler.TraceAnnotation("engine.emit"):
                # logical decode work this step: one forward + one sampling
                # decision per ACTIVE slot (idle-slot padding is not logical
                # work — counting it would break batch-composition
                # invariance); redundant comparator re-reads beyond the first
                # are priced per active slot the same way
                self.backend.note_call(
                    SP.analog_call_profile(
                        "serve_step", batch=len(active),
                        redundant=(r_eff - 1) * len(active),
                    )
                )
                now = time.perf_counter()
                self._decode_time += now - t_dec
                self._occ_sum += len(active) / self.cfg.max_batch
                self._decode_steps += 1
                for req in active:
                    slot = req.slot
                    if sane_np is not None and int(sane_np[slot]):
                        # logit-sanity trip (analog garbage / injected
                        # fault): evict with the matching typed reason
                        # instead of publishing a garbage token — the slot
                        # frees, serving continues
                        self._evict_request(
                            req,
                            SP.SANITY_REASONS.get(int(sane_np[slot]), "nan"),
                            now,
                        )
                        continue
                    t = int(nxt_np[slot])
                    rep = self._replay.get(req.rid)
                    if rep is not None:
                        # teacher-force the next already-published token (the
                        # sampled one is bitwise the same in a fault-free
                        # run); nothing re-records or re-publishes
                        self._tokens[slot] = rep.pop(0)
                        if not rep:
                            del self._replay[req.rid]
                        self._steps[slot] += 1
                        continue
                    self._tokens[slot] = t
                    self._steps[slot] += 1
                    self._total_tokens += 1
                    self.sched.record_token(req, t, self.cfg.eos_token, now)
                    self._release_if_done(req)
                    emitted.append((req.rid, t))
        self._busy_time += time.perf_counter() - t_start
        return emitted

    # ---- degraded-device serving: detection + mitigation + policy ----

    def _fault_pass(self) -> None:
        """Per-tick fault housekeeping, before any scheduling decision:
        advance the backend's fault clock, rebuild stale entry points,
        and fire the known-answer canary on its interval (a failure is a
        detection event and may trigger tile retirement)."""
        bk = self.backend
        if getattr(bk, "overrides_compute", False):
            bk.advance_clock(1)
        self._check_fault_version()
        ci = self.cfg.canary_interval
        if not ci or self._ticks % ci:
            return
        self._canary_probes += 1
        if self._canary_expected is None:
            self._canary_expected = KOPS.canary_expected()
        key = jax.random.fold_in(self._base_key, 0xCA9A30 + self._ticks)
        got = np.asarray(self._canary(key), np.float32)
        exp = self._canary_expected
        scale = max(float(np.max(np.abs(exp))), 1e-9)
        rel = float(np.max(np.abs(got - exp))) / scale
        passed = rel <= self.cfg.canary_threshold
        self._tick_canary = passed
        if passed:
            return
        self._canary_failures += 1
        self._tick_dirty += 1
        thr = self.cfg.tile_retire_threshold
        if thr > 0.0 and hasattr(bk, "retire_tiles"):
            if bk.retire_tiles(thr):
                # retirement changed the stuck masks baked into traces
                self._check_fault_version()

    def _redundant_effective(self) -> int:
        """Redundant-read factor for this tick's decode step: the config
        base, raised to the policy's factor at degradation level >= 2."""
        r = self._redundant_base
        pol = self.cfg.degradation
        if pol is not None and self._degrade_level >= 2 and self.mcfg.wta_head:
            r = max(r, pol.redundant_reads)
        return r

    def _degrade_transition(self, to: int, why: str) -> None:
        self._degraded_transitions.append({
            "tick": self._ticks,
            "from": self._degrade_level,
            "to": to,
            "why": why,
        })
        self._degrade_level = to

    def _policy_update(self) -> None:
        """End-of-tick DegradationPolicy step: fold this tick's detection
        events (sanity evictions + canary failure) into the streaks and
        move at most one rung.  Escalation needs ``trip_after``
        consecutive dirty ticks; de-escalation needs ``recover_after``
        consecutive clean canary PASSES — absent a canary there is no
        positive evidence of recovery, so degradation is one-way."""
        pol = self.cfg.degradation
        if pol is None:
            return
        if self._tick_dirty:
            self._dirty_streak += 1
            self._clean_streak = 0
        else:
            self._dirty_streak = 0
            if self._tick_canary is True:
                self._clean_streak += 1
        if self._dirty_streak >= pol.trip_after and self._degrade_level < 3:
            self._degrade_transition(
                self._degrade_level + 1, "fault_pressure"
            )
            self._dirty_streak = 0
        elif (
            self._clean_streak >= pol.recover_after
            and self._degrade_level > 0
        ):
            self._degrade_transition(
                self._degrade_level - 1, "canary_recovered"
            )
            self._clean_streak = 0

    def _spec_viable(self, active: list[Request]) -> bool:
        """True when a k-deep draft run cannot write past ``max_len`` for
        any decoding slot (overruns past a slot's RESERVATION are fine —
        they land in the trash page — but a write past the table width
        would clamp into the slot's own last block)."""
        lim = self.cfg.max_len - self.spec_k
        return all(int(self._host_pos[r.slot]) <= lim for r in active)

    def _spec_tick(self, active: list[Request], emitted: list) -> None:
        """One fused self-speculative round for every decoding slot.

        Device side: ONE dispatch drafts k chained tokens per slot (the
        plain decode cell, K/V into the reserved pages, identical int8
        ``quant_step`` trajectory) and re-decodes the run read-only from
        the pre-draft snapshot (see :func:`SP.make_paged_spec_round`).
        Host side: per slot, accept drafts until the verifier's resample
        disagrees — the disagreeing resample is itself the corrected
        token, exactly what the plain engine would have emitted, so
        greedy and per-slot-keyed WTA streams stay byte-identical to
        ``speculate_k=0``.  A rejected (or short) round rolls the slot
        back through the verifier's per-step states; drafted K/V beyond
        the rollback position is masked dead rows.  The NaN guard moves
        to draft depth: a non-finite draft step truncates the usable run
        and, if everything before it accepted, evicts with reason
        ``"nan"`` exactly like a plain tick would have.
        """
        k = self.spec_k
        w = self._window_blocks(active, k)
        pre_pos = self._host_pos.copy()
        pre_steps = self._steps.copy()
        self._cache, dtoks, doks, vtoks, _voks, vstates = self._spec_round(
            self.params,
            self._cache,
            self._put(self._table[:, :w], "table"),
            self._put(self._tokens, "slot_vec"),
            self._put(self._req_keys, "slot_keys"),
            self._put(self._steps, "slot_vec"),
        )
        d_np = np.asarray(dtoks)   # device sync — decode_time is honest
        # one fused round: k drafted tokens per active slot (forwarded,
        # sampled, K/V written) + k verify re-decodes (forwarded, sampled,
        # read-only).  Rejected drafts stay in the tally — that energy was
        # spent whether or not a token publishes.
        self.backend.note_call(
            SP.analog_call_profile("spec_round", batch=len(active), k=k)
        )
        dok_np = np.asarray(doks)
        v_np = np.asarray(vtoks)
        self._host_pos += k  # mirrors the draft scan's k pos bumps
        now = time.perf_counter()
        self._occ_sum += len(active) / self.cfg.max_batch
        self._decode_steps += 1
        self._spec_rounds += 1
        for req in active:
            slot = req.slot
            # usable drafts stop at the first non-finite draft step
            m = k
            for j in range(k):
                if not bool(dok_np[slot, j]):
                    m = j
                    break
            if m == 0:
                self._evict_request(req, "nan", now)
                continue
            self._spec_drafted += m
            req.spec_drafted += m
            req.spec_high = max(req.spec_high, int(pre_pos[slot]) + m - 1)
            e = 0              # tokens consumed from this round
            done = False
            rollback_at = None  # verify-state index to roll back to
            for i in range(m):
                t_d = int(d_np[slot, i])
                rep = self._replay.get(req.rid)
                if rep is not None:
                    # teacher-forced replay of already-published tokens:
                    # consumed without re-recording.  A forced token that
                    # disagrees with its draft (possible only under
                    # injected faults) truncates the round right there —
                    # the verifier state after consuming the inputs so
                    # far is still published-stream-exact
                    forced = rep.pop(0)
                    if not rep:
                        del self._replay[req.rid]
                    self._tokens[slot] = forced
                    e += 1
                    if forced != t_d:
                        rollback_at = i
                        break
                    continue
                t = int(v_np[slot, i])  # == draft when accepted
                self._tokens[slot] = t
                e += 1
                accepted = t == t_d
                if accepted:
                    self._spec_accepted += 1
                    req.spec_accepted += 1
                self._total_tokens += 1
                done = self.sched.record_token(
                    req, t, self.cfg.eos_token, now
                )
                emitted.append((req.rid, t))
                if done:
                    break
                if not accepted:
                    rollback_at = i
                    break
            self._spec_emitted += e
            if done:
                self._release_if_done(req)
                continue
            if rollback_at is not None:
                # rejected tail: rewind pos + recurrent/SSM state to the
                # verifier's recomputed state after the last consumed
                # input — bitwise the plain engine's state at that point
                self._cache = self._spec_rollback(
                    self._cache,
                    vstates,
                    self._put(np.int32(rollback_at), "replicated"),
                    self._put(np.int32(slot), "replicated"),
                )
                self._host_pos[slot] = int(pre_pos[slot]) + e
            elif m < k:
                # every usable draft accepted and the NEXT draft step went
                # non-finite from exactly this state — the plain engine's
                # next tick would have hit the same logits
                self._evict_request(req, "nan", now)
                continue
            self._steps[slot] = int(pre_steps[slot]) + e

    def _cow_pass(self, active: list[Request], span: int = 1) -> None:
        """Resolve copy-on-write state BEFORE the batched decode step.

        Each active slot is about to write its K/V row into block
        ``pos // block_size`` of its table — or, for a speculative round,
        into every block the k-deep draft run touches
        (``span`` > 1; only the FIRST can be shared, since decode-budget
        blocks past the prompt boundary are always freshly reserved, so
        the one-spare-per-request COW invariant holds unchanged).  If a
        write-span page is still shared
        (refcount > 1) the writer forks: its reserved spare page gets a
        device-side copy of the pristine content and the table row is
        repointed, so the write lands privately while the other owners
        keep reading the original.  A *sole* owner writes in place, but
        its page's index entry (if any) is dropped first — the content is
        about to diverge from the registered hash, and a stale entry
        would hand corrupted blocks to later admissions.

        The one writer per shared page that holds no spare is its original
        registrant (sharers always reserve a spare at the gate); every
        co-writer of that page forks in this same pass — all copies read
        the still-pristine page because the in-place write only happens
        inside the decode step, after this pass completes.
        """
        bs = self.cfg.kv_block_size
        for req in active:
            p = int(self._host_pos[req.slot])
            last = min((p + span - 1) // bs, self._max_blocks - 1)
            for wb in range(p // bs, last + 1):
                page = int(self._table[req.slot, wb])
                if page < self.blocks.n_reserved:
                    continue  # trash row of an already-evicted slot
                if (
                    self.blocks.refcount(page) > 1
                    and self.blocks.spare_count(req.rid) > 0
                ):
                    _, new = self.blocks.cow_fork(req.rid, wb)
                    self._cache = self._page_copy(self._cache, page, new)
                    self._table[req.slot, wb] = new
                    self._cow_forks += 1
                else:
                    self.blocks.deregister(page)  # no-op if unregistered

    def _window_blocks(self, active: list[Request], span: int = 1) -> int:
        """Decode window width in blocks for this tick.

        The smallest power-of-two block count covering every active slot's
        current position (plus the ``span`` positions a speculative round
        writes) — power-of-two bucketing keeps the number of distinct
        (table-width) step compiles logarithmic in max_len while the
        window still tracks the *occupied* prefix, not max_len."""
        bs = self.cfg.kv_block_size
        need = max(
            (int(self._host_pos[r.slot]) + span - 1) // bs + 1
            for r in active
        )
        w = 1
        while w < need:
            w *= 2
        return min(w, self._max_blocks)

    def run(self) -> dict[int, list[int]]:
        """Drain queue + slots; returns {rid: generated tokens}."""
        while self.sched.has_work():
            self.tick()
        return {
            r.rid: r.output
            for r in self.sched.all_requests()
            if r.state is RequestState.DONE
        }

    def step(self) -> list[list[int]]:
        """Legacy API: drain and return newly completed outputs in
        submission order (the old static engine's ``step()`` contract)."""
        before = {
            r.rid
            for r in self.sched.all_requests()
            if r.state is RequestState.DONE
        }
        self.run()
        return [
            r.output
            for r in self.sched.all_requests()
            if r.state is RequestState.DONE and r.rid not in before
        ]

    def metrics(self) -> ServingMetrics:
        done = [
            r
            for r in self.sched.all_requests()
            if r.state is RequestState.DONE
        ]
        ttfts = [r.ttft for r in done if r.ttft is not None]
        evictions: dict[str, int] = {}
        for r in done:
            if r.done_reason:
                evictions[r.done_reason] = (
                    evictions.get(r.done_reason, 0) + 1
                )
        by_class: dict[int, dict] = {}
        for pr in sorted({r.priority for r in done}):
            rs = [r for r in done if r.priority == pr]
            tt = [r.ttft for r in rs if r.ttft is not None]
            lat = [
                r.done_time - r.submit_time
                for r in rs
                if r.done_time is not None
            ]
            by_class[pr] = {
                "n": len(rs),
                "ttft_p50_ms": _pctl(tt, 50) * 1e3,
                "ttft_p99_ms": _pctl(tt, 99) * 1e3,
                "latency_p50_ms": _pctl(lat, 50) * 1e3,
                "latency_p99_ms": _pctl(lat, 99) * 1e3,
            }
        wall = self._busy_time
        analog = self.backend.snapshot(published_tokens=self._total_tokens)
        return ServingMetrics(
            completed=len(done),
            total_tokens=self._total_tokens,
            wall_time=wall,
            tokens_per_s=self._total_tokens / max(wall, 1e-9),
            ttft_mean=float(np.mean(ttfts)) if ttfts else 0.0,
            decode_steps=self._decode_steps,
            prefills=self._prefills,
            occupancy_mean=self._occ_sum / max(self._decode_steps, 1),
            decode_time=self._decode_time,
            prefix_hits=self._prefix_hits,
            cow_forks=self._cow_forks,
            prefix_partial_hits=self._prefix_partial_hits,
            prefill_tokens=self._prefill_tokens,
            prefill_tokens_saved=self._prefill_tokens_saved,
            ttft_p50=_pctl(ttfts, 50),
            ttft_p99=_pctl(ttfts, 99),
            admissions=self._admissions,
            queue_wait_s=self._queue_wait,
            preemptions=self._preemptions,
            restores=self._restores,
            spill_drops=self._spill_drops,
            spec_rounds=self._spec_rounds,
            spec_drafted=self._spec_drafted,
            spec_accepted=self._spec_accepted,
            spec_acceptance=(
                self._spec_accepted / max(self._spec_drafted, 1)
            ),
            spec_tokens_per_round=(
                self._spec_emitted / max(self._spec_rounds, 1)
            ),
            evictions=evictions,
            latency_by_class=by_class,
            analog=analog,
            degraded_mode=self._degrade_level,
            canary_probes=self._canary_probes,
            canary_failures=self._canary_failures,
            retired_tiles=int(getattr(self.backend, "retired_tiles", 0)),
            redundant_read_events=analog["redundant_read_events"],
            degraded_transitions=list(self._degraded_transitions),
        )

    def compile_counts(self) -> dict[str, int]:
        """Traced-computation counts per jitted entry point.

        The recompile-guard tests pin these.  Paged: one compile per
        (bucket, suffix-chunk shape) pair for the chunked prefill entry
        point and one per decode window bucket (serve_step) — never one
        per tick, slot, page set, or start position (those are traced).
        The sharing entry points (state_insert, page_copy, sample0)
        compile at most ONCE each over the engine's lifetime: their
        argument shapes are bucket-independent.  Dense: one compile per
        prefill bucket (prefill + insert).

        ``serve_step`` sums over the redundant-read variants: a level-2
        degradation episode adds one compile per (R, window) pair, and
        the healthy artifact is reused when the ladder recovers."""
        if self.paged:
            counts = {
                "serve_step": sum(
                    f._cache_size() for f in self._serve_steps.values()
                )
            }
        else:
            counts = {"serve_step": self._serve_step._cache_size()}
        if self.paged:
            counts["suffix_prefill"] = self._suffix_prefill._cache_size()
            counts["state_insert"] = self._state_insert._cache_size()
            counts["page_copy"] = self._page_copy._cache_size()
            counts["sample0"] = self._sample0._cache_size()
            counts["page_spill"] = self._page_spill._cache_size()
            counts["page_restore"] = self._page_restore._cache_size()
            counts["state_gather"] = self._state_gather._cache_size()
            if self.spec_k:
                counts["spec_round"] = self._spec_round._cache_size()
                counts["spec_rollback"] = (
                    self._spec_rollback._cache_size()
                )
        else:
            counts["prefill"] = self._prefill._cache_size()
            counts["insert"] = self._insert._cache_size()
        return counts


class StaticServingEngine:
    """The pre-continuous-batching reference: whole batch prefilled
    together (prompts left-padded to the batch max), every slot held until
    the LAST request of the batch finishes.  Kept as the equivalence oracle
    for tests and the occupancy baseline for benchmarks."""

    def __init__(self, params, model_cfg: ModelConfig, cfg: ServeConfig):
        self.params = params
        self.mcfg = model_cfg
        self.cfg = cfg
        self.fns = get_model_fns(model_cfg)
        self._serve_step = jax.jit(
            SP.make_serve_step(model_cfg), donate_argnums=(1,)
        )
        self._queue: list[tuple[list[int], int, float]] = []
        self._key = jax.random.PRNGKey(cfg.seed)
        self._occ_sum = 0.0
        self._decode_steps = 0
        self._total_tokens = 0
        self._busy_time = 0.0
        self._decode_time = 0.0
        self._ttfts: list[float] = []
        self._completed = 0

    def submit(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: Optional[int] = None,
        submit_time: Optional[float] = None,
    ) -> None:
        budget = (
            self.cfg.max_new_tokens if max_new_tokens is None
            else max_new_tokens
        )
        if budget < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {budget}")
        if len(prompt_tokens) + budget > self.cfg.max_len:
            raise ValueError(
                f"prompt {len(prompt_tokens)} + {budget} new tokens "
                f"exceeds cache max_len={self.cfg.max_len}"
            )
        self._queue.append(
            (
                list(prompt_tokens),
                budget,
                submit_time if submit_time is not None
                else time.perf_counter(),
            )
        )

    def pending(self) -> int:
        """Requests queued for a future batch wave."""
        return len(self._queue)

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    def step(self) -> list[list[int]]:
        """Serve one batch from the queue; returns generated token lists."""
        if not self._queue:
            return []
        t_start = time.perf_counter()
        batch = self._queue[: self.cfg.max_batch]
        self._queue = self._queue[self.cfg.max_batch :]
        prompts = [p for p, _, _ in batch]
        budgets = [m for _, m, _ in batch]
        b = len(prompts)
        plen = max(len(p) for p in prompts)
        # decode starts at the batch-max padded length for EVERY slot, so a
        # short prompt co-batched with a long one can overflow the cache
        # even when its own (prompt + budget) fit at submit time
        worst = plen + max(budgets)
        if worst > self.cfg.max_len:
            raise ValueError(
                f"padded prompt window {plen} + max budget {max(budgets)} "
                f"= {worst} exceeds cache max_len={self.cfg.max_len}"
            )
        toks = np.asarray(
            [left_pad(p, plen) for p in prompts], np.int32
        )
        cache, logits = self.fns.prefill(
            self.params, {"tokens": jnp.asarray(toks)}, self.mcfg,
            self.cfg.max_len,
        )
        out = [[] for _ in range(b)]
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        done = np.zeros(b, bool)
        now = time.perf_counter()
        for _, _, t_sub in batch:
            self._ttfts.append(now - t_sub)
        for _ in range(max(budgets)):
            for i in range(b):
                if not done[i]:
                    t = int(token[i])
                    out[i].append(t)
                    self._total_tokens += 1
                    if t == self.cfg.eos_token or len(out[i]) >= budgets[i]:
                        done[i] = True
            if done.all():
                break
            key = self._next_key() if self.mcfg.wta_head else None
            t_dec = time.perf_counter()
            cache, token = self._serve_step(self.params, cache, token, key)
            token.block_until_ready()
            self._decode_time += time.perf_counter() - t_dec
            # slots stay held for the whole batch: idle ones count against
            # occupancy, which is the cost continuous batching removes
            self._occ_sum += (b - int(done.sum())) / self.cfg.max_batch
            self._decode_steps += 1
        self._completed += b
        self._busy_time += time.perf_counter() - t_start
        return out

    def run(self) -> list[list[int]]:
        outs: list[list[int]] = []
        while self._queue:
            outs.extend(self.step())
        return outs

    def metrics(self) -> ServingMetrics:
        wall = self._busy_time
        return ServingMetrics(
            completed=self._completed,
            total_tokens=self._total_tokens,
            wall_time=wall,
            tokens_per_s=self._total_tokens / max(wall, 1e-9),
            ttft_mean=float(np.mean(self._ttfts)) if self._ttfts else 0.0,
            decode_steps=self._decode_steps,
            prefills=self._completed,
            occupancy_mean=self._occ_sum / max(self._decode_steps, 1),
            decode_time=self._decode_time,
        )
