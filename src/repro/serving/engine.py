"""Collaborative serving engine: the paper's system with a real model inside.

A model is partitioned into ``cfg.num_stages`` stages; each stage ``h`` is
served by ``n_h`` replica groups (on a real cluster: mesh slices; here:
logical replicas with Jetson-profiled service rates).  The engine:

  * routes each request hop-by-hop by sampling the DTO-EE offloading
    strategy ``p`` (the control plane runs the genuine RUR/RUS rounds on a
    Topology mirroring the replica layout);
  * runs the REAL stage forward for the data plane — the residual stream is
    handed replica-to-replica, and exit decisions use the model's actual
    branch confidences against the thresholds C (not a table);
  * advances a simulated clock with M/D/1 FIFO service at each replica, so
    measured delays follow the same queueing physics the optimizer models.

Data plane (autoregressive, cache-threaded, continuously batched):

``serve(..., gen_len=N)`` decodes up to N tokens per request.  A request's
first pass is a *prefill* hop chain: stage 1 embeds the prompt, every stage
runs the full-sequence forward, and — in cached mode — writes its stage-local
KV/state caches into a **slot** of that replica's resident cache store.  The
route sampled on this first pass is pinned per stage (``Request.path``), so
each later token returns to the replicas that hold its caches.  Every
subsequent token is a *decode* hop chain: stage 1 embeds one token, each
stage runs a one-token cached step — per-row positions, attention through
``kernels.ops.decode_attention`` (the Pallas flash-decode kernel on TPU) —
so per-token work is O(1) in the prefix length instead of the O(prefix)
re-prefill of the stateless baseline (``decode_mode="stateless"`` keeps that
baseline runnable for A/B benchmarks).  For expanded-attention configs (GQA
/ SSM blocks) the two modes — and the monolithic ``model.prefill`` +
``model.decode_step`` reference — emit bitwise token-identical sequences;
MLA configs decode through the absorbed-latent math, which matches the
monolithic decode reference but, like all absorbed MLA inference, is not
bitwise-equal to re-expanded full-sequence attention.

Continuous batching: replicas own a ring of cache slots.  Whenever a replica
frees at a stage boundary it forms the next batch from whatever waits —
newly-arrived prompts are admitted into free slots alongside in-flight decode
rows, and rows that take an early exit retire immediately, releasing their
slots at every replica on their path without stalling the rest of the batch.
Both the early-exit branches and the final head go through the fused
``exit_confidence`` kernel, so ``[B, vocab]`` logits never touch HBM.

Exit semantics per token: the first branch with conf >= c_h emits the token
and terminates the request (a confident answer); otherwise the final head's
token is appended and decoding continues to ``gen_len``.  ``gen_len=1``
reproduces the paper's single-shot classification plane exactly.

This is deliberately a single-process, event-stepped engine: the
distributed *semantics* (who talks to whom, what information each node has,
which replica holds which cache rows) are faithful; only the transport is
in-process.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from collections import deque
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import dto_ee
from repro.core import topology as topo_lib
from repro.core.simulator import RoutingCdf
from repro.core.thresholds import ExitProfile
from repro.core.types import DtoHyperParams, ModelProfile, Topology
from repro.models import model as model_lib
from repro.runtime import elastic
from repro.serving import steps
from repro.serving.batching import (
    ExitPredictor,
    Request,
    ShapeBucketBatcher,
    SlotRing,
    batch_tokens,
    pack_decode_batch,
    padded_batch_size,
    pow2_floor,
)
from repro.obs import host
from repro.obs.stream import build_stream
from repro.serving.paging import BlockAllocator


def _thinned_arrivals(
    rng: np.random.Generator,
    base_rate: float,
    factor,
    f_max: float,
    n: int,
) -> np.ndarray:
    """Non-homogeneous Poisson arrival times for ``n`` requests by thinning:
    candidates arrive at ``base_rate * f_max`` and are accepted with
    probability ``factor(t) / f_max`` (the scenario's piecewise arrival-rate
    modulation, e.g. a burst window)."""
    lam = base_rate * max(f_max, 1e-12)
    out = np.empty(n, np.float64)
    t = 0.0
    k = 0
    while k < n:
        t += rng.exponential(1.0 / lam)
        if rng.random() * f_max <= factor(t):
            out[k] = t
            k += 1
    return out


# ---------------------------------------------------------------------------
# Stage programs: one jitted program per stage / head, traced per batch shape
# ---------------------------------------------------------------------------


class StagePrograms:
    """Compiled per-stage forwards + fused heads of a partitioned model.

    One jitted callable per stage and per head; jax re-traces per input
    shape, so every (stage, padded-batch shape) bucket compiles once and is
    then served from the executable cache.  The cached-decode plane adds a
    per-stage prefill (cache-building), slot-write (scatter into the
    replica's resident store), and cached one-token decode program.

    The programs are handed ``weights``, the serving copy of ``params``
    (``model.serving_params``): every leaf they read only through a cast to
    the compute dtype is held in it, once, so no call casts the weights
    again.  Setting ``params`` makes the copy anew.
    """

    def __init__(self, params: Any, cfg: ArchConfig):
        self.cfg = cfg
        self.params = params
        self._embed = steps.make_embed_step(cfg)
        self._stage = {}
        self._exit = {}
        self._final = steps.make_final_head_step(cfg)
        self._prefill = {}
        self._decode = {}
        self._slot_write = {}
        self._paged_decode = {}
        self._paged_write = {}
        self._block_copy = {}

    @property
    def params(self) -> Any:
        """The parameter tree as given."""
        return self._params

    @params.setter
    def params(self, params: Any) -> None:
        self._params = params
        self.weights = None  # free the old copy before making the new one
        self.weights = model_lib.serving_params(params, self.cfg)
        dtype = jnp.dtype(self.cfg.dtype)
        held = kept = 0
        for a in jax.tree.leaves(self.weights):
            if a.dtype == dtype:
                held += a.nbytes
            else:
                kept += a.nbytes
        # bytes the programs read in the compute dtype, and bytes in any other
        self.weight_bytes = {"dtype": dtype.name, "held": held, "kept": kept}

    def embed(self, tokens: jnp.ndarray) -> jnp.ndarray:
        return self._embed(self.weights, tokens)

    def run_stage(self, stage_idx: int, x: jnp.ndarray) -> jnp.ndarray:
        """Forward hidden states through stage ``stage_idx`` (1-indexed)."""
        if stage_idx not in self._stage:
            self._stage[stage_idx] = steps.make_stage_forward(self.cfg, stage_idx)
        return self._stage[stage_idx](self.weights, x)

    def stage_prefill(self, stage_idx: int, x: jnp.ndarray, max_len: int):
        """(x_out, stage caches [n_periods, B, max_len, ...]) for one stage."""
        key = (stage_idx, max_len)
        if key not in self._prefill:
            self._prefill[key] = steps.make_stage_prefill(self.cfg, stage_idx, max_len)
        return self._prefill[key](self.weights, x)

    def stage_decode(self, stage_idx: int, x, slot_caches, slots):
        """One cached token per row against the replica's (donated) store."""
        if stage_idx not in self._decode:
            self._decode[stage_idx] = steps.make_stage_decode(self.cfg, stage_idx)
        return self._decode[stage_idx](self.weights, x, slot_caches, slots)

    def slot_write(self, stage_idx: int, slot_caches, new_caches, slots):
        if stage_idx not in self._slot_write:
            self._slot_write[stage_idx] = steps.make_slot_write(self.cfg, stage_idx)
        return self._slot_write[stage_idx](slot_caches, new_caches, slots)

    def init_slot_caches(self, stage_idx: int, num_slots: int, max_len: int):
        return model_lib.init_stage_slot_caches(self.cfg, stage_idx, num_slots, max_len)

    # -- paged layout -------------------------------------------------------
    def init_paged_slot_caches(
        self, stage_idx: int, num_slots: int, num_blocks: int, block_size: int,
        max_len: int,
    ):
        return model_lib.init_stage_paged_caches(
            self.cfg, stage_idx, num_slots, num_blocks, block_size, max_len
        )

    def paged_slot_write(self, stage_idx, pool, state, new_caches, wtab, slots):
        if stage_idx not in self._paged_write:
            self._paged_write[stage_idx] = steps.make_paged_slot_write(
                self.cfg, stage_idx
            )
        return self._paged_write[stage_idx](pool, state, new_caches, wtab, slots)

    def paged_stage_decode(self, stage_idx, x, pool, state, tables, slots, seq_len):
        key = (stage_idx, seq_len)
        if key not in self._paged_decode:
            self._paged_decode[key] = steps.make_paged_stage_decode(
                self.cfg, stage_idx, seq_len
            )
        return self._paged_decode[key](self.weights, x, pool, state, tables, slots)

    def block_copy(self, stage_idx, pool, src, dst):
        if stage_idx not in self._block_copy:
            self._block_copy[stage_idx] = steps.make_block_copy(self.cfg, stage_idx)
        return self._block_copy[stage_idx](pool, src, dst)

    def exit_head(self, stage_idx: int, x_last: jnp.ndarray):
        """(confidence, token) of the exit branch after stage ``stage_idx``."""
        if stage_idx not in self._exit:
            self._exit[stage_idx] = steps.make_exit_head_step(self.cfg, stage_idx)
        return self._exit[stage_idx](self.weights, x_last)

    def final_head(self, x_last: jnp.ndarray):
        """(confidence, token) of the final head — fused, no [B, vocab] logits."""
        return self._final(self.weights, x_last)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeStats:
    delays: list = dataclasses.field(default_factory=list)
    exit_stage: list = dataclasses.field(default_factory=list)
    confidences: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)  # last emitted token
    rids: list = dataclasses.field(default_factory=list)
    gen_tokens: list = dataclasses.field(default_factory=list)  # full sequences
    arrivals: list = dataclasses.field(default_factory=list)
    dones: list = dataclasses.field(default_factory=list)
    num_batches: int = 0
    num_forward_rows: int = 0  # padded rows pushed through stage forwards
    num_real_rows: int = 0  # live rows among them (the rest is padding waste)
    # in-flight pressure: live (admitted, unretired) requests over time
    peak_in_flight: int = 0
    # paged layout: prompt blocks served from the prefix map vs allocated,
    # and pool occupancy sampled at every paged batch (per replica)
    prefix_hit_blocks: int = 0
    prefix_total_blocks: int = 0
    block_occupancy: list = dataclasses.field(default_factory=list)
    # online control plane: mid-serve strategy installs, failure re-executions,
    # and the straggler monitor's end-of-serve capacity estimates per ES
    num_reconfigs: int = 0
    reconfig_times: list = dataclasses.field(default_factory=list)
    resubmitted: int = 0
    capacity_estimates: dict = dataclasses.field(default_factory=dict)
    # observability: the SpanTracer / MetricsCollector attached to the serve
    # (None when tracing was off — the zero-cost path)
    trace: Any = None
    metrics: Any = None
    # the host-span record of the call (repro.obs.host): per-span count,
    # total and self seconds, stage batches, compiles by span
    host: dict | None = None
    # StagePrograms.weight_bytes: the serving weights' bytes held in the
    # compute dtype and kept as given
    weights: dict | None = None

    def summary(self) -> dict:
        d = np.asarray(self.delays)
        es = np.asarray(self.exit_stage)
        total_tokens = int(sum(len(g) for g in self.gen_tokens))
        makespan = (
            float(max(self.dones) - min(self.arrivals)) if self.dones else float("nan")
        )
        out = {
            "num_completed": int(d.size),
            "mean_delay": float(d.mean()) if d.size else float("nan"),
            "delay_std": float(d.std()) if d.size else float("nan"),
            "p50_delay": float(np.percentile(d, 50)) if d.size else float("nan"),
            "p95_delay": float(np.percentile(d, 95)) if d.size else float("nan"),
            "p99_delay": float(np.percentile(d, 99)) if d.size else float("nan"),
            "exit_histogram": {
                int(s): int((es == s).sum()) for s in np.unique(es)
            },
            "num_batches": self.num_batches,
            # padded-row waste: fraction of stage-forward rows that were
            # shape-padding rather than live requests
            "num_forward_rows": self.num_forward_rows,
            "num_real_rows": self.num_real_rows,
            "padded_row_frac": (
                1.0 - self.num_real_rows / self.num_forward_rows
                if self.num_forward_rows
                else 0.0
            ),
            "generated_tokens": total_tokens,
            "sim_tokens_per_s": (
                total_tokens / makespan if makespan and makespan > 0 else float("nan")
            ),
            "peak_in_flight": self.peak_in_flight,
            # paged-layout memory stats (zeros/nan under the dense layout)
            "prefix_hit_blocks": self.prefix_hit_blocks,
            "prefix_total_blocks": self.prefix_total_blocks,
            "prefix_hit_rate": (
                self.prefix_hit_blocks / self.prefix_total_blocks
                if self.prefix_total_blocks
                else 0.0
            ),
            "block_occupancy_mean": (
                float(np.mean(self.block_occupancy))
                if self.block_occupancy
                else float("nan")
            ),
            "block_occupancy_peak": (
                float(np.max(self.block_occupancy))
                if self.block_occupancy
                else float("nan")
            ),
            # online control plane
            "num_reconfigs": self.num_reconfigs,
            "resubmitted": self.resubmitted,
            "capacity_estimates": dict(self.capacity_estimates),
        }
        if self.trace is not None:
            from repro.obs.attribution import decompose

            dec = decompose(self.trace, self)
            out["delay_components"] = dec["mean_components_s"]
            out["per_stage_components"] = dec["per_stage"]
        return out

    def report(self) -> dict:
        """Machine-readable serve report: the summary, the host-span
        record, plus, when a tracer was attached, the full per-request delay
        decomposition and, when a metrics collector was attached, its
        registry snapshot."""
        out = {"summary": self.summary()}
        if self.host is not None:
            out["host"] = self.host
        if self.weights is not None:
            out["weights"] = self.weights
        if self.trace is not None:
            from repro.obs.attribution import decompose

            out["decomposition"] = decompose(self.trace, self)
        if self.metrics is not None:
            out["metrics"] = self.metrics.snapshot()
        return out

    def by_rid(self) -> dict[int, tuple[int, int]]:
        """rid -> (exit_stage, token); completion-order independent view."""
        return {
            r: (s, t)
            for r, s, t in zip(self.rids, self.exit_stage, self.tokens)
        }

    def sequences_by_rid(self) -> dict[int, tuple[int, tuple[int, ...]]]:
        """rid -> (exit_stage, full token sequence)."""
        return {
            r: (s, tuple(g))
            for r, s, g in zip(self.rids, self.exit_stage, self.gen_tokens)
        }


class CollaborativeEngine:
    """End-to-end: Poisson arrivals -> DTO-EE routing -> staged model."""

    def __init__(
        self,
        params: Any,
        cfg: ArchConfig,
        topo: Topology,
        profile: ModelProfile,
        exit_profile: ExitProfile,
        hyper: DtoHyperParams | None = None,
        seed: int = 0,
    ):
        if topo.num_stages != cfg.num_stages:
            raise ValueError("topology stages must match the model's stages")
        self.programs = StagePrograms(params, cfg)
        self.cfg = cfg
        self.topo = topo
        self.profile = profile
        self.exit_profile = exit_profile
        self.hyper = hyper or DtoHyperParams()
        self.rng = np.random.default_rng(seed)
        self.state = dto_ee.init_state(topo, profile, exit_profile)
        self._round_step = dto_ee.make_round_step(topo, profile, self.hyper)
        self.stage_to_branch = {
            s: b for b, s in enumerate(exit_profile.branch_stage[:-1])
        }
        # live capacity tracker: every stage batch folds its (GFLOPs, wall)
        # into the EWMA, so a throttled replica's estimate sinks even while
        # the optimizer's view (self.topo) is stale — the measurement half
        # of the closed control loop.  Estimates persist across serves and
        # topology swaps (node ids are stable).
        self.straggler = elastic.StragglerMonitor.from_topology(topo)

    # -- control plane ------------------------------------------------------
    def update_topology(self, new_topo: Topology) -> None:
        """Dynamic environment: capacities / arrival rates changed between
        slots.  The offloading state (p, thresholds) warm-starts; only the
        jitted round program is rebuilt (mu / rates are baked into it)."""
        if new_topo.num_edges != self.topo.num_edges:
            raise ValueError("edge set changed; use runtime.elastic helpers first")
        self.topo = new_topo
        self._round_step = dto_ee.make_round_step(new_topo, self.profile, self.hyper)

    def configuration_phase(self, adapt_thresholds: bool = True) -> None:
        """One time-slot configuration update (Algorithm 3)."""
        with host.span("engine.configure"):
            res = dto_ee.run_configuration_phase(
                self.topo,
                self.profile,
                self.exit_profile,
                self.hyper,
                state=self.state,
                adapt_thresholds=adapt_thresholds,
                round_step=self._round_step,
            )
        self.state = res.state

    @property
    def p(self) -> np.ndarray:
        return np.asarray(self.state.carry.p, np.float64)

    @property
    def thresholds(self) -> np.ndarray:
        return self.state.thresholds

    # -- data plane ---------------------------------------------------------
    def _stage_input(
        self,
        stage: int,
        reqs: list[Request],
        batch_size: int,
        pad_to: int | None = None,
    ):
        """Assemble one batch's host input: the padded [B, S] tokens at
        stage 1 (for ``programs.embed``), the padded [B, S, d] residual
        stream after it.

        Hidden states travel between replicas as host numpy buffers (the
        in-process stand-in for the network hop), so batch assembly is one
        concatenate + one upload instead of per-request device ops.
        ``pad_to`` right-pads the token batch to a fixed sequence length
        (stateless decode passes: a fixed shape keeps every pass's reductions
        length-stable, so re-prefill stays bitwise identical to the
        fixed-arena cached path — and one compiled program serves all steps).
        """
        if stage == 1:
            toks = batch_tokens(reqs, batch_size)
            if pad_to is not None and toks.shape[1] < pad_to:
                toks = np.pad(toks, ((0, 0), (0, pad_to - toks.shape[1])))
            return toks
        hs = [r.hidden for r in reqs]
        B = padded_batch_size(len(reqs), batch_size)
        if B > len(reqs):
            hs.append(np.zeros((B - len(reqs),) + hs[0].shape[1:], hs[0].dtype))
        # host buffer goes straight into the jitted stage (jit device_puts it)
        return np.concatenate(hs, axis=0) if len(hs) > 1 else hs[0]

    def serve(
        self,
        prompts: list[np.ndarray],
        duration: float = 5.0,
        arrival_rate: float | None = None,
        batch_size: int = 1,
        gen_len: int = 1,
        decode_mode: str | None = None,
        num_slots: int | None = None,
        cache_layout: str = "dense",
        block_size: int = 16,
        num_blocks: int | None = None,
        prefix_sharing: bool = True,
        batch_policy: str = "fifo",
        controller=None,
        scenario=None,
        telemetry=None,
        tracer=None,
        metrics=None,
    ) -> ServeStats:
        """Serve ``prompts`` arriving as a Poisson stream.

        Arrivals are a genuine Poisson process at ``arrival_rate`` (default:
        the topology's total external rate ``phi_ext.sum()``); ``duration``
        is only the fallback window when no positive rate exists.  Arrival
        nodes are sampled proportional to each end device's external rate
        ``phi_ext`` — the data plane sees the same traffic mix the optimizer
        models.  Each request autoregressively decodes up to ``gen_len``
        tokens (1 = the paper's single-shot classification); a token taken at
        an early-exit branch terminates its request.  ``batch_size`` sets the
        per-replica micro-batch width.  ``decode_mode``:

          * ``"cached"``    (default for gen_len > 1): stage-local KV caches
            live in per-replica slot rings; decode steps are one-token cached
            programs and new prompts are admitted into running batches at
            stage boundaries (continuous batching).
          * ``"stateless"`` (default for gen_len == 1): every token re-runs
            the full prefix through each stage — the re-prefill baseline.

        Both modes emit token-identical sequences and exit decisions for
        expanded-attention configs (see the module docstring for the MLA
        absorbed-decode caveat).

        ``cache_layout`` picks the slot-store memory layout for cached mode:

          * ``"dense"`` — each slot reserves a worst-case ``max_len`` KV
            arena (the bitwise reference baseline).
          * ``"paged"`` — KV lives in a per-replica pool of ``block_size``-
            token blocks (``num_blocks`` of them; default: the dense
            footprint) addressed through per-request block tables, allocated
            lazily as generations grow.  Identical prompt-prefix blocks are
            shared across requests (``prefix_sharing``) with copy-on-write,
            so a replica holds several times more in-flight requests in the
            same KV bytes.  Emitted tokens and exits are bitwise identical
            to the dense layout; admission additionally waits for pool
            blocks, and a serve whose pool is too small for its working set
            raises instead of deadlocking silently.

        Online control plane (``repro.control``):

          * ``telemetry`` — a streaming sink (``Telemetry`` or anything with
            its hook methods) receiving per-arrival / per-batch /
            per-transfer / per-exit observations as the simulated clock
            advances.

        Observability (``repro.obs``): ``telemetry``, ``tracer`` and
        ``metrics`` all subscribe to ONE instrumentation stream — a single
        set of emission points on the engine's hot paths
        (:mod:`repro.obs.stream`).  ``tracer`` (a ``SpanTracer``) builds one
        span tree per request tiling ``[arrival, retirement]`` exactly —
        admission wait, per-hop transfer, queue wait, batch-formation wait,
        stage compute — plus instants and counter samples, all on the
        modelled clock.  ``metrics`` (a ``MetricsCollector``) feeds a
        metrics registry (p50/p95/p99 delay, batch occupancy, pool
        occupancy, realized exit pairs).  With none attached the stream is
        ``None`` and every emission site is skipped — the disabled path is
        bitwise identical.  Attached observers land on ``stats.trace`` /
        ``stats.metrics`` for ``ServeStats.report()`` and the exporters.
        Independently of them, the call's host work on the real clock is
        kept in ``serve.*`` spans (:mod:`repro.obs.host`): profiler
        annotations, and one record of per-span times and compiles on
        ``stats.host``.
          * ``controller`` — a ``ReconfigController``; every
            ``controller.interval`` sim-seconds it plans a reconfiguration
            from the telemetry's measured topology and, after the plan's
            decision time has elapsed (routing stays on the stale strategy
            meanwhile, as the paper charges slow deciders), atomically
            installs the new ``p``/thresholds into the engine.
          * ``scenario`` — a ``Scenario`` of timed environment
            perturbations (bursts, slowdowns, link degradation, node
            failure).  Physics then run on a private copy of the serve-time
            topology: ``self.topo`` stays the optimizer's view and only
            learns of the drift through telemetry + reconfiguration.
            Failure events re-execute every task resident on the dead
            replica from its source ED and require the stateless
            single-shot plane (gen_len=1); cache migration is a follow-on.
          * ``batch_policy="threshold"`` — threshold-aware batch packing:
            decode batches are filled with rows sharing the head row's
            predicted retirement class (confidence history vs the *current*
            thresholds) so batches retire together, and takes are trimmed
            to exact padded shapes — recovering ``padded_row_frac`` waste
            with token-identical outputs.
        """
        rec = host.Record()
        with host.span("serve.setup", rec):
            if batch_size < 1:
                raise ValueError("batch_size must be >= 1")
            if gen_len < 1:
                raise ValueError("gen_len must be >= 1")
            if cache_layout not in ("dense", "paged"):
                raise ValueError("cache_layout must be 'dense' or 'paged'")
            paged = cache_layout == "paged"
            if decode_mode is None:
                decode_mode = "cached" if (gen_len > 1 or paged) else "stateless"
            if decode_mode not in ("cached", "stateless"):
                raise ValueError("decode_mode must be 'cached' or 'stateless'")
            if paged and decode_mode != "cached":
                raise ValueError("cache_layout='paged' requires decode_mode='cached'")
            if paged and block_size < 1:
                raise ValueError("block_size must be >= 1")
            cached = decode_mode == "cached"
            if gen_len > 1 and self.cfg.frontend != "tokens":
                raise ValueError("autoregressive decode needs a token frontend")
            if any(int(p.shape[0]) < 1 for p in prompts):
                raise ValueError("prompts must be non-empty")
            if batch_policy not in ("fifo", "threshold"):
                raise ValueError("batch_policy must be 'fifo' or 'threshold'")
            if controller is not None and telemetry is None:
                telemetry = controller.telemetry
            if scenario is not None and any(
                ev.kind == "fail" for ev in scenario.events
            ) and (cached or gen_len > 1):
                raise ValueError(
                    "failure scenarios re-execute tasks from their source ED and "
                    "need the stateless single-shot plane (gen_len=1, "
                    "decode_mode='stateless'); cache migration is a follow-on"
                )
            profile = self.profile
            if scenario is not None:
                # physics run on a PRIVATE copy of the serve-time topology: the
                # scenario mutates physical truth, while self.topo remains the
                # optimizer's view and only learns of the drift through
                # telemetry + reconfiguration (the closed loop under test)
                topo = dataclasses.replace(
                    self.topo,
                    mu=self.topo.mu.copy(),
                    phi_ext=self.topo.phi_ext.copy(),
                    edge_rate=self.topo.edge_rate.copy(),
                )
            else:
                topo = self.topo
            programs = self.programs
            H = profile.num_stages
            eds = topo.nodes_at_stage(0)
            rate = (
                float(arrival_rate)
                if arrival_rate is not None
                else float(topo.phi_ext.sum())
            )
            n = len(prompts)
            if rate > 0 and np.isfinite(rate):
                if scenario is not None and scenario.modulates_arrivals:
                    arrivals = _thinned_arrivals(
                        self.rng,
                        rate,
                        scenario.arrival_factor,
                        scenario.max_arrival_factor,
                        n,
                    )
                else:
                    arrivals = np.cumsum(self.rng.exponential(1.0 / rate, size=n))
            else:
                arrivals = np.sort(self.rng.uniform(0.0, duration, size=n))
            # arrival nodes follow the optimizer's traffic model: each request
            # lands on an ED with probability proportional to its phi_ext
            ed_w = topo.phi_ext[eds]
            if n and ed_w.sum() > 0:
                if scenario is not None and scenario.modulates_eds:
                    # scenario skews WHICH devices produce during its windows
                    ed_idx = np.empty(n, np.int64)
                    for i, t in enumerate(arrivals):
                        w = scenario.ed_weights(float(t), eds, ed_w)
                        ed_idx[i] = self.rng.choice(len(eds), p=w / w.sum())
                else:
                    ed_idx = self.rng.choice(len(eds), size=n, p=ed_w / ed_w.sum())
            else:
                ed_idx = np.arange(n) % max(len(eds), 1)
            packer = None
            if batch_policy == "threshold":
                # reads self.thresholds lazily, so mid-serve reconfigurations
                # re-aim the exit predictions immediately
                packer = ExitPredictor(lambda: self.thresholds, gen_len)
            # one capacity EWMA, not two: the telemetry adopts the engine's
            # monitor so the capacity_estimates reported in ServeStats are
            # exactly the numbers the controller planned from
            shared_monitor = telemetry is not None and hasattr(
                telemetry, "attach_monitor"
            )
            if shared_monitor:
                telemetry.attach_monitor(self.straggler)
            # every observer subscribes to one instrumentation stream; None when
            # nothing is attached, so the disabled path skips every emission
            stream = build_stream(telemetry, tracer, metrics)

            stats = ServeStats()
            stats.weights = dict(programs.weight_bytes)
            stats.trace = tracer
            stats.metrics = metrics
            # one precomputed CDF serves every routing sample (shared with the
            # simulator); the controller's installs and node failures rebuild it
            route = RoutingCdf(topo, self.p)
            # event heap: (time, seq, kind, payload)
            #   kind 0: transfer done, request joins ``node``   payload (req, node)
            #   kind 1: batch service done at ``node``          payload (node, reqs,
            #           conf [B] | None, tok [B] | None, is_decode_pass)
            #   kind 2: control plane                           payload ("scenario",
            #           event idx) | ("reconfig",) | ("install", plan)
            #   kind 3: deferred ED arrival (scenario runs only; the first hop's
            #           transfer time must see the environment AT arrival time)
            #           payload: req
            heap: list = []
            dead_nodes: set[int] = set()
            seq = itertools.count()
            wait_seq = itertools.count()  # FIFO order shared across queue kinds
            es_nodes = [int(v) for v in range(topo.num_nodes) if topo.node_stage[v] > 0]
            pending = {v: ShapeBucketBatcher(batch_size, seq=wait_seq) for v in es_nodes}
            busy_until = {v: 0.0 for v in es_nodes}
            decode_q: dict[int, deque] = {v: deque() for v in es_nodes}
            rings: dict[int, SlotRing] = {}
            slot_store: dict[int, Any] = {}
            pool_store: dict[int, Any] = {}
            state_store: dict[int, Any] = {}
            allocators: dict[int, BlockAllocator] = {}
            trash = -1
            trash_block = -1
            n_logical = 0
            max_len = max((int(p.shape[0]) for p in prompts), default=1) + gen_len
            if cached:
                n_slots = num_slots if num_slots is not None else max(2 * batch_size, 4)
                trash = n_slots  # extra store row absorbing padded-row writes
                if paged:
                    n_logical = -(-max_len // block_size)
                    # default pool: the dense layout's footprint, block-granular
                    n_blocks = (
                        num_blocks if num_blocks is not None else n_slots * n_logical
                    )
                    trash_block = n_blocks  # extra pool row absorbing trash writes
                    for v in es_nodes:
                        rings[v] = SlotRing(n_slots)
                        allocators[v] = BlockAllocator(
                            n_blocks, block_size, prefix_sharing=prefix_sharing
                        )
                        pool_store[v], state_store[v] = programs.init_paged_slot_caches(
                            int(topo.node_stage[v]),
                            n_slots + 1,
                            n_blocks + 1,
                            block_size,
                            max_len,
                        )
                else:
                    for v in es_nodes:
                        rings[v] = SlotRing(n_slots)
                        slot_store[v] = programs.init_slot_caches(
                            int(topo.node_stage[v]), n_slots + 1, max_len
                        )
            live_reqs = 0  # admitted somewhere, not yet retired
            # paged admission reserves each row's worst-case REMAINING blocks
            # (it can still write up to prompt + gen_len - 1 positions), so a
            # live row's decode appends can never starve — deadlock-freedom
            # without preemption.  The occupancy win over dense comes from
            # reserving each request's OWN worst case instead of max_len, plus
            # prefix sharing keeping actual allocation below the reservation.
            reserved = {v: 0 for v in es_nodes} if paged else {}

            def total_blocks(prompt_len: int) -> int:
                return -(-(prompt_len + gen_len - 1) // block_size)

            def run_prefill(node: int, reqs: list[Request], now: float) -> None:
                nonlocal live_reqs
                h = int(topo.node_stage[node])
                where = {"stage": h, "node": node, "rows": len(reqs)}
                # stateless decode passes run at a FIXED padded length: causal
                # masking makes the pad rows inert, the valid rows stay bitwise
                # identical to the fixed-size cached arena, and one compiled
                # program serves every step of the generation
                stateless_decode = not cached and reqs[0].phase == "decode"
                pad_to = max_len if stateless_decode else None
                with host.span("serve.assemble", rec):
                    x_in = self._stage_input(h, reqs, batch_size, pad_to=pad_to)
                if h == 1:
                    with host.span("serve.embed", rec, **where):
                        x_in = programs.embed(x_in)
                if cached:
                    with host.span("serve.stage_prefill", rec, **where):
                        x, caches = programs.stage_prefill(h, x_in, max_len)
                    with host.span("serve.assemble", rec):
                        slots = np.full((int(x.shape[0]),), trash, np.int32)
                        for i, r in enumerate(reqs):
                            s = rings[node].alloc()
                            assert s is not None, "dispatch admitted beyond ring capacity"
                            if not r.slots:  # first residency anywhere: now in flight
                                live_reqs += 1
                                stats.peak_in_flight = max(stats.peak_in_flight, live_reqs)
                            r.slots[node] = s
                            slots[i] = s
                        if paged:
                            alloc = allocators[node]
                            wtab = np.full(
                                (int(x.shape[0]), n_logical), trash_block, np.int32
                            )
                            batch_hits = batch_total = 0
                            for i, r in enumerate(reqs):
                                res = alloc.alloc(r.tokens.tolist())
                                assert res is not None, (
                                    "dispatch admitted beyond block-pool capacity"
                                )
                                r.block_seq[node] = res.handle
                                reserved[node] += total_blocks(r.prompt_len) - len(res.table)
                                for j, (blk, shared) in enumerate(
                                    zip(res.table, res.shared)
                                ):
                                    # shared blocks already hold this prefix — never
                                    # rewrite them (other rows read them); redirect
                                    # the write to the trash block
                                    wtab[i, j] = trash_block if shared else blk
                                batch_hits += sum(res.shared)
                                batch_total += len(res.table)
                            stats.prefix_hit_blocks += batch_hits
                            stats.prefix_total_blocks += batch_total
                    if paged:
                        with host.span("serve.paged_slot_write", rec, **where):
                            pool_store[node], state_store[node] = programs.paged_slot_write(
                                h, pool_store[node], state_store[node], caches, wtab, slots
                            )
                        stats.block_occupancy.append(alloc.used_fraction)
                        if stream is not None:
                            with host.span("serve.emit", rec):
                                stream.on_pool(
                                    now, node, alloc.used_fraction,
                                    batch_hits, batch_total,
                                )
                    else:
                        with host.span("serve.slot_write", rec, **where):
                            slot_store[node] = programs.slot_write(
                                h, slot_store[node], caches, slots
                            )
                else:
                    with host.span("serve.run_stage", rec, **where):
                        x = programs.run_stage(h, x_in)
                last = (
                    int(reqs[0].all_tokens().shape[0]) if stateless_decode else None
                )
                finish_pass(
                    node, reqs, x, now, h, is_decode_pass=False, last_valid=last,
                )

            def run_decode(node: int, reqs: list[Request], now: float) -> None:
                h = int(topo.node_stage[node])
                B = len(reqs)
                where = {"stage": h, "node": node, "rows": B}
                Bp = padded_batch_size(B, batch_size)
                with host.span("serve.assemble", rec):
                    slots = np.full((Bp,), trash, np.int32)
                    for i, r in enumerate(reqs):
                        slots[i] = r.slots[node]
                    if h == 1:
                        x_in = np.zeros((Bp, 1), np.int32)
                        for i, r in enumerate(reqs):
                            x_in[i, 0] = r.generated[-1]
                    else:
                        hs = [r.hidden for r in reqs]
                        if Bp > B:
                            hs.append(np.zeros((Bp - B,) + hs[0].shape[1:], hs[0].dtype))
                        x_in = np.concatenate(hs, axis=0) if len(hs) > 1 else hs[0]
                    if paged:
                        alloc = allocators[node]
                        rtab = np.full((Bp, n_logical), trash_block, np.int32)
                        for i, r in enumerate(reqs):
                            # grow the row by one position (dispatch budgeted this);
                            # crossing a block boundary takes a fresh pool block, and
                            # a fork-shared target block is copied before the write
                            res = alloc.append(r.block_seq[node])
                            assert res is not None, (
                                "dispatch scheduled a decode row beyond pool capacity"
                            )
                            if res.new_block:
                                reserved[node] -= 1  # consumed part of the reservation
                            # the engine never forks and shares only full blocks
                            # strictly inside the prompt, while appends target
                            # pos >= prompt_len — so copy-on-write cannot trigger
                            # here (a reachable COW would also need charging against
                            # ``reserved``; see programs.block_copy for the device
                            # half when preemption/fork lands)
                            assert res.cow is None, "append hit a shared block"
                            tab = alloc.table(r.block_seq[node])
                            rtab[i, : len(tab)] = tab
                if h == 1:
                    with host.span("serve.embed", rec, **where):
                        x_in = programs.embed(x_in)
                if paged:
                    with host.span("serve.paged_stage_decode", rec, **where):
                        x, pool_store[node], state_store[node] = programs.paged_stage_decode(
                            h, x_in, pool_store[node], state_store[node], rtab, slots,
                            max_len,
                        )
                    stats.block_occupancy.append(alloc.used_fraction)
                    if stream is not None:
                        with host.span("serve.emit", rec):
                            stream.on_pool(now, node, alloc.used_fraction)
                else:
                    with host.span("serve.stage_decode", rec, **where):
                        x, slot_store[node] = programs.stage_decode(
                            h, x_in, slot_store[node], slots
                        )
                finish_pass(node, reqs, x, now, h, is_decode_pass=True)

            def finish_pass(
                node: int,
                reqs: list[Request],
                x,
                now: float,
                h: int,
                is_decode_pass: bool,
                last_valid: int | None = None,
            ) -> None:
                """Shared tail of a stage batch: heads, handoff buffers, clock.

                ``last_valid`` points the heads at the last REAL position of a
                right-padded stateless decode pass (the heads otherwise read the
                final position).
                """
                b = self.stage_to_branch.get(h)
                head = "final_head" if h == H else "exit_head" if b is not None else None
                conf = tok = None
                if head is not None:
                    with host.span("serve." + head, rec, stage=h, node=node, rows=len(reqs)):
                        x_heads = (
                            x if last_valid is None else x[:, last_valid - 1 : last_valid]
                        )
                        if h == H:
                            conf, tok = programs.final_head(x_heads)
                        else:
                            conf, tok = programs.exit_head(h, x_heads)
                # waiting for the device, apart from copying its outputs.  Each
                # copy is requested before its wait, so the runtime starts it
                # the moment the output is ready (as a bare np.asarray would);
                # the residual is copied while the heads still run
                if h < H:
                    x.copy_to_host_async()
                    with host.span("serve.wait", rec):
                        x.block_until_ready()
                    with host.span("serve.pull", rec):
                        x_np = np.asarray(x)
                        for i, r in enumerate(reqs):
                            r.hidden = x_np[i : i + 1]
                if conf is not None:
                    conf.copy_to_host_async()
                    tok.copy_to_host_async()
                    with host.span("serve.wait", rec):
                        jax.block_until_ready((conf, tok))
                    with host.span("serve.pull", rec):
                        conf = np.asarray(conf)[: len(reqs)]
                        tok = np.asarray(tok)[: len(reqs)]
                stats.num_batches += 1
                rec.batches += 1
                stats.num_forward_rows += int(x.shape[0])
                stats.num_real_rows += len(reqs)
                if is_decode_pass:
                    # clock model: alpha[h] is the profiled cost of one TASK
                    # (= its prompt) at stage h, so one cached token is charged
                    # that task's per-token share, alpha / prompt_len — O(1) in
                    # the prefix versus the full alpha a stateless re-prefill
                    # pass pays
                    gflops = profile.alpha[h - 1] * sum(
                        1.0 / r.prompt_len for r in reqs
                    )
                else:
                    gflops = len(reqs) * profile.alpha[h - 1]
                service = gflops / float(topo.mu[node])
                start = max(now, busy_until[node])
                done = start + service
                busy_until[node] = done
                # every batch is a capacity measurement: the EWMA follows the
                # replica's TRUE (possibly scenario-perturbed) rate, feeding the
                # controller's effective topology (telemetry.on_batch folds the
                # observation into the shared monitor; observe directly only
                # when no telemetry shares it)
                if not shared_monitor:
                    self.straggler.observe(node, gflops, service)
                if stream is not None:
                    with host.span("serve.emit", rec):
                        stream.on_batch(
                            done,
                            node,
                            gflops,
                            service,
                            len(pending[node]) + len(decode_q[node]),
                            stage=h,
                            rids=tuple(r.rid for r in reqs),
                            t_dispatch=now,
                            t_start=start,
                            n_rows=int(x.shape[0]),
                            is_decode=is_decode_pass,
                        )
                heapq.heappush(
                    heap, (done, next(seq), 1, (node, reqs, conf, tok, is_decode_pass))
                )

            def dispatch(node: int, now: float) -> None:
                """If ``node`` is free, form one batch and run it.

                FIFO across work kinds by arrival order, except that prompts
                blocked on slot space never stall waiting decode rows — that is
                the continuous-batching invariant.
                """
                if now < busy_until[node]:
                    return
                ph = pending[node].head_seq()
                prompt_blocks = 0
                if ph is not None and cached and rings[node].available == 0:
                    ph = None  # admission blocked until a retirement frees a slot
                if ph is not None and paged:
                    # admission also waits for pool blocks: each admitted row
                    # reserves its sharing-blind worst-case TOTAL (prompt +
                    # generation), so in-flight decode appends can never starve
                    _, head = pending[node].peek()
                    prompt_blocks = total_blocks(head.prompt_len)
                    if allocators[node].free_blocks - reserved[node] < prompt_blocks:
                        ph = None
                dq = decode_q[node]
                if paged and dq:
                    # take FIFO decode rows whose next-position block needs fit
                    # the pool right now; rows that can't extend wait without
                    # masking runnable work behind them
                    budget = allocators[node].free_blocks
                    take: list = []
                    rest: list = []
                    for item in dq:
                        cost = allocators[node].append_cost(item[1].block_seq[node])
                        if len(take) < batch_size and cost <= budget:
                            take.append(item)
                            budget -= cost
                        else:
                            rest.append(item)
                    if packer is not None and take:
                        # threshold-aware packing on top of the budget filter:
                        # group the eligible rows by predicted retirement class
                        # and trim to an exact padded shape; bumped rows rejoin
                        # the queue in FIFO (seq) order
                        take, back = pack_decode_batch(take, batch_size, packer)
                        rest = sorted(back + rest)
                    dh = take[0][0] if take else None
                else:
                    take = rest = []
                    dh = dq[0][0] if dq else None
                if ph is None and dh is None:
                    return
                if dh is not None and (ph is None or dh < ph):
                    if paged:
                        dq.clear()
                        dq.extend(rest)
                        reqs = [r for _, r in take]
                    elif packer is not None:
                        take, rest = pack_decode_batch(list(dq), batch_size, packer)
                        dq.clear()
                        dq.extend(rest)
                        reqs = [r for _, r in take]
                    else:
                        reqs = [dq.popleft()[1] for _ in range(min(batch_size, len(dq)))]
                    run_decode(node, reqs, now)
                    return
                max_take = rings[node].available if cached else None
                if paged:
                    headroom = allocators[node].free_blocks - reserved[node]
                    max_take = min(max_take, headroom // max(prompt_blocks, 1))
                if packer is not None:
                    # trim the prefill take so the padded batch holds no dead
                    # rows (padded_batch_size pads to the next power of two)
                    head_len = pending[node].head_len()
                    cap = min(head_len, batch_size)
                    if max_take is not None:
                        cap = min(cap, max_take)
                    if cap >= 1:
                        trim = pow2_floor(cap)
                        max_take = trim if max_take is None else min(max_take, trim)
                popped = pending[node].pop_batch(max_take)
                if popped is None:
                    return
                _, reqs = popped
                run_prefill(node, reqs, now)

            def enqueue(req: Request, node: int, now: float) -> None:
                h = int(topo.node_stage[node])
                req.node = node
                req.stage = h
                if stream is not None:
                    with host.span("serve.emit", rec):
                        stream.on_enqueue(now, req.rid, node)
                if req.phase == "decode" and cached:
                    decode_q[node].append((next(wait_seq), req))
                else:
                    if req.phase == "decode":
                        # stateless decode pass: padded shapes are uniform, so
                        # bucket by the VALID prefix length (heads slice there)
                        key = ("dec", int(req.all_tokens().shape[0]))
                    elif h == 1:
                        key = ("tok", int(req.all_tokens().shape[0]))
                    else:
                        key = ("hid", tuple(req.hidden.shape[1:]))
                    pending[node].push(key, req)
                dispatch(node, now)

            def finish(req: Request, done: float, c: float, h: int) -> None:
                nonlocal live_reqs
                req.exited, req.exit_stage = True, h
                req.confidence, req.output_token = c, req.generated[-1]
                req.t_done = done
                stats.delays.append(req.delay)
                stats.exit_stage.append(h)
                stats.confidences.append(c)
                stats.tokens.append(req.generated[-1])
                stats.rids.append(req.rid)
                stats.gen_tokens.append(tuple(req.generated))
                stats.arrivals.append(req.arrival)
                stats.dones.append(done)
                if stream is not None:
                    with host.span("serve.emit", rec):
                        stream.on_exit(done, req.rid, h, c)
                if cached and req.slots:
                    live_reqs -= 1
                    freed = list(req.slots.items())
                    req.slots = {}
                    for v, s in freed:
                        rings[v].free(s)
                    if paged:
                        for v, handle in req.block_seq.items():
                            # release the unused tail of the worst-case reservation
                            reserved[v] -= total_blocks(req.prompt_len) - len(
                                allocators[v].table(handle)
                            )
                            allocators[v].free(handle)
                        req.block_seq = {}
                    for v, _ in freed:
                        # a freed slot/block can unblock admission-waiting
                        # prompts and pool-starved decode rows
                        if pending[v].head_seq() is not None or (
                            paged and decode_q[v]
                        ):
                            dispatch(v, done)

            def submit(req: Request, t: float) -> None:
                """First hop: sample a stage-1 replica and ship the raw task."""
                nxt, e = route.sample(self.rng, req.ed)
                req.path[1] = (nxt, int(e))
                t_cm = profile.beta[0] / float(topo.edge_rate[e])
                if stream is not None:
                    with host.span("serve.emit", rec):
                        stream.on_submit(t, req.rid, req.ed, req.arrival)
                        stream.on_transfer(
                            t, t + t_cm, t_cm, req.ed, nxt, req.rid, profile.beta[0]
                        )
                heapq.heappush(heap, (t + t_cm, next(seq), 0, (req, nxt)))

            def resubmit(req: Request, now: float) -> None:
                """Fail-stop re-execution: a task resident on (or in flight to) a
                failed replica restarts from scratch at its source ED."""
                stats.resubmitted += 1
                req.attempts += 1
                req.phase = "prefill"
                req.hidden = None
                req.generated.clear()
                req.path.clear()
                req.last_conf.clear()
                if stream is not None:
                    with host.span("serve.emit", rec):
                        stream.on_resubmit(now, req.rid)
                submit(req, now)

            for i, (t, prompt) in enumerate(zip(arrivals, prompts)):
                ed = int(eds[ed_idx[i]])
                req = Request(
                    rid=i, tokens=np.asarray(prompt, np.int32), arrival=t, ed=ed
                )
                if scenario is not None:
                    # defer the first hop to arrival time so it sees the
                    # environment (link rates, routing strategy) AS OF ``t``
                    heapq.heappush(heap, (float(t), next(seq), 3, req))
                else:
                    submit(req, t)

            if scenario is not None:
                for i, ev in enumerate(scenario.events):
                    heapq.heappush(heap, (float(ev.time), next(seq), 2, ("scenario", i)))
            if controller is not None:
                heapq.heappush(
                    heap,
                    (float(controller.interval), next(seq), 2, ("reconfig",)),
                )

        while heap:
            if len(stats.delays) == n:
                break  # all requests measured; only control events remain
            with host.span("serve.event", rec):
                now, _, kind, payload = heapq.heappop(heap)
                if kind == 3:  # deferred ED arrival
                    submit(payload, now)
                    continue
                if kind == 2:  # control plane
                    tag = payload[0]
                    if tag == "scenario":
                        ev = scenario.events[payload[1]]
                        if ev.kind == "fail":
                            # (cached failure was rejected up front: no request
                            # can hold cache residency at the dead replica)
                            dead = int(ev.node)
                            # detection is instant: view AND environment drop the
                            # dead replica's edges in lockstep (same predicate, so
                            # structures stay aligned), the surviving strategy is
                            # renormalized, and the optimizer warm-starts from it
                            new_view, p_new = elastic.handle_failure(
                                self.topo, self.p, dead
                            )
                            env_new = (
                                new_view
                                if topo is self.topo
                                else topo_lib.with_node_failure(topo, dead)
                            )
                            self.topo = new_view
                            self.state = dataclasses.replace(
                                self.state,
                                carry=self.state.carry._replace(
                                    p=jnp.asarray(p_new, jnp.float32)
                                ),
                            )
                            self._round_step = dto_ee.make_round_step(
                                new_view, profile, self.hyper
                            )
                            topo = env_new
                            route = RoutingCdf(topo, self.p)
                            dead_nodes.add(dead)
                            self.straggler.mu_hat[dead] = 1e-9
                            if stream is not None:
                                with host.span("serve.emit", rec):
                                    stream.on_failure(now, dead)
                            # tasks queued at the dead replica re-execute from
                            # their source EDs (in-service and in-flight ones are
                            # caught at their event pops via ``dead_nodes``)
                            while True:
                                popped = pending[dead].pop_batch()
                                if popped is None:
                                    break
                                for r in popped[1]:
                                    resubmit(r, now)
                        else:
                            scenario.apply_env(ev, topo)
                    elif tag == "reconfig":
                        with host.span("engine.configure", rec):
                            plan = controller.plan(self, now)
                        if plan is not None:
                            # routing stays on the stale strategy until the
                            # decision time has elapsed — slow reconfigurations
                            # pay for their latency exactly as in the paper
                            heapq.heappush(
                                heap,
                                (
                                    now + plan.decision_time,
                                    next(seq),
                                    2,
                                    ("install", plan),
                                ),
                            )
                        # reschedule only while data-plane events remain: a
                        # starved serve must drain to the loud stall check below
                        # instead of ticking forever
                        if any(ev[2] != 2 for ev in heap):
                            heapq.heappush(
                                heap,
                                (now + controller.interval, next(seq), 2, ("reconfig",)),
                            )
                    else:  # install
                        if controller.install(self, payload[1]):
                            route = RoutingCdf(topo, self.p)
                            stats.num_reconfigs += 1
                            stats.reconfig_times.append(now)
                    continue
                if kind == 0:
                    req, node = payload
                    if node in dead_nodes:
                        resubmit(req, now)
                        continue
                    if stream is not None and req.stage == 0:
                        with host.span("serve.emit", rec):
                            stream.on_arrival(req.arrival, req.ed, req.rid)
                    enqueue(req, node, now)
                    continue
                # kind 1: batch done — batched exit decision already on device
                node, reqs, conf, tok, is_decode_pass = payload
                if node in dead_nodes:
                    # the replica died mid-service: its output is lost, the
                    # whole batch re-executes from the source EDs
                    for req in reqs:
                        resubmit(req, now)
                    continue
                h = int(topo.node_stage[node])
                b = self.stage_to_branch.get(h)
                for i, req in enumerate(reqs):
                    if h == H:
                        req.generated.append(int(tok[i]))
                        if len(req.generated) >= gen_len:
                            finish(req, now, float(conf[i]), h)
                            continue
                        # loop back for the next token: one-token payload to the
                        # request's pinned stage-1 replica
                        req.phase = "decode"
                        node1, e1 = req.path[1]
                        t_cm = (
                            profile.beta[0]
                            / float(topo.edge_rate[e1])
                            / req.prompt_len
                        )
                        if stream is not None:
                            # telemetry never saw this hop pre-refactor (the
                            # modeled per-token payload is not a fresh link
                            # observation), so it is a distinct event the
                            # tracer consumes and the estimators ignore
                            with host.span("serve.emit", rec):
                                stream.on_loopback(
                                    now, now + t_cm, node, node1, req.rid,
                                    profile.beta[0] / req.prompt_len,
                                )
                        heapq.heappush(heap, (now + t_cm, next(seq), 0, (req, node1)))
                        continue
                    if b is not None:
                        # confidence history feeds the threshold-aware packer's
                        # exit predictions for this row's NEXT token
                        req.last_conf[b] = float(conf[i])
                        if float(conf[i]) >= self.thresholds[b]:
                            # confident early exit: emit and retire
                            req.generated.append(int(tok[i]))
                            finish(req, now, float(conf[i]), h)
                            continue
                    nh = h + 1
                    if nh in req.path:
                        nxt, e = req.path[nh]
                    else:
                        nxt, e = route.sample(self.rng, node)
                        req.path[nh] = (nxt, int(e))
                    t_cm = profile.beta[h] / float(topo.edge_rate[e])
                    if is_decode_pass:
                        t_cm /= req.prompt_len
                    if stream is not None:
                        with host.span("serve.emit", rec):
                            stream.on_transfer(
                                now,
                                now + t_cm,
                                t_cm,
                                node,
                                nxt,
                                req.rid,
                                profile.beta[h] / (req.prompt_len if is_decode_pass else 1),
                            )
                    heapq.heappush(heap, (now + t_cm, next(seq), 0, (req, nxt)))
                dispatch(node, now)

        with host.span("serve.finish", rec):
            stats.capacity_estimates = {
                int(v): float(self.straggler.mu_hat[v]) for v in es_nodes
            }
            if len(stats.delays) != n:
                # a stall is resource starvation no future event can clear —
                # fail loudly rather than silently drop requests
                hint = (
                    "the KV block pool cannot cover the in-flight working set — "
                    "raise num_blocks, shrink num_slots, or use "
                    "cache_layout='dense'"
                    if paged
                    else "requests were left queued with no runnable work"
                )
                raise RuntimeError(
                    f"serve stalled with {n - len(stats.delays)} of {n} requests "
                    f"unfinished; {hint}"
                )
        stats.host = rec.close()
        return stats
