"""The benchmark's subscriber to the engine's instrumentation stream.

Attached through ``serve(telemetry=...)``, it takes the host clock at the
engine's own hooks: ``on_submit`` (the engine takes the request up),
``on_loopback`` and ``on_exit`` (a token reached the host: the engine has
pulled the heads' outputs by then).  ``on_batch`` gives the real rows of
each stage batch.  It also keeps what the heads returned for each row, so
the comparison with the reference reads every confidence the timed path
produced, and it can wrap the stage programs in profiler annotations so a
trace can say what the host did in each idle gap.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from time import perf_counter

import numpy as np

# StagePrograms calls that run device work, as the annotations name them
PROGRAM_CALLS = (
    "embed",
    "stage_prefill",
    "stage_decode",
    "slot_write",
    "exit_head",
    "final_head",
)
ANNOTATION_PREFIX = "bench."


@dataclasses.dataclass
class Batch:
    round: int
    stage: int
    is_decode: bool
    rids: tuple


class Recorder:
    """Wall times, batches and head outputs of every round it sees."""

    def __init__(self, programs, num_stages: int, exit_stages, annotate: bool = False):
        self.H = num_stages
        self.head_stages = set(exit_stages) | {num_stages}
        self.reset()
        self._wrap(programs, annotate)

    def reset(self) -> None:
        """Forget every round seen so far."""
        self.round = -1
        self.prompts: dict = {}  # (round, rid) -> prompt length
        self.t_submit: dict = {}
        self.t_tokens: dict = defaultdict(list)
        self.batches: list[Batch] = []
        # (round, rid) -> [ {stage: (conf, tok)} per token index ]
        self.heads: dict = defaultdict(list)
        self._final_passes: dict = defaultdict(int)
        self._last_head = None

    # -- program wrappers ----------------------------------------------------
    def _wrap(self, programs, annotate: bool) -> None:
        import jax

        for name in PROGRAM_CALLS:
            fn = getattr(programs, name)
            keep = name in ("exit_head", "final_head")
            label = ANNOTATION_PREFIX + name
            setattr(programs, name, self._wrapped(fn, keep, label if annotate else None, jax))

    def _wrapped(self, fn, keep: bool, label, jax):
        def call(*args, **kwargs):
            if label is None:
                out = fn(*args, **kwargs)
            else:
                with jax.profiler.TraceAnnotation(label):
                    out = fn(*args, **kwargs)
            if keep:
                self._last_head = out
            return out

        return call

    # -- rounds --------------------------------------------------------------
    def begin_round(self, index: int, prompts) -> None:
        self.round = index
        for rid, p in enumerate(prompts):
            self.prompts[(index, rid)] = int(p.shape[0])

    # -- stream hooks --------------------------------------------------------
    def on_submit(self, t, rid, ed, arrival, **_):
        self.t_submit[(self.round, rid)] = perf_counter()

    def on_loopback(self, t0, t1, src, dst, rid, mb, **_):
        self.t_tokens[(self.round, rid)].append(perf_counter())

    def on_exit(self, t, rid, stage, conf, **_):
        self.t_tokens[(self.round, rid)].append(perf_counter())

    def on_batch(self, done, node, gflops, wall, queue_depth, *, stage, rids, is_decode, **_):
        self.batches.append(Batch(self.round, stage, bool(is_decode), tuple(rids)))
        if stage not in self.head_stages:
            return
        conf, tok = self._last_head
        self._last_head = None
        conf = np.asarray(conf)[: len(rids)]
        tok = np.asarray(tok)[: len(rids)]
        for i, rid in enumerate(rids):
            key = (self.round, rid)
            k = self._final_passes[key]
            per_token = self.heads[key]
            while len(per_token) <= k:
                per_token.append({})
            per_token[k][stage] = (float(conf[i]), int(tok[i]))
            if stage == self.H:
                self._final_passes[key] += 1

    # -- readings ------------------------------------------------------------
    def context_length(self, batch: Batch, rid) -> int:
        """Positions a row attends over in this pass: its prompt, plus one
        per token it had produced before a decode pass (after
        ``index_decode_positions``)."""
        done = self._positions[id(batch)][rid] if batch.is_decode else 0
        return self.prompts[(batch.round, rid)] + done

    def index_decode_positions(self) -> None:
        """Walk the batches in order and note, for each decode row, how many
        tokens its request had produced before that pass."""
        done = defaultdict(int)
        self._positions = {}
        for b in self.batches:
            if b.is_decode:
                self._positions[id(b)] = {r: done[(b.round, r)] for r in b.rids}
            if b.stage == self.H:
                for r in b.rids:
                    done[(b.round, r)] += 1

    def requests(self, rounds=None):
        keys = [k for k in self.t_submit if rounds is None or k[0] in rounds]
        return sorted(keys)

    def ttft_s(self, rounds=None) -> np.ndarray:
        return np.asarray(
            [self.t_tokens[k][0] - self.t_submit[k] for k in self.requests(rounds)
             if self.t_tokens[k]]
        )

    def itl_s(self, rounds=None) -> np.ndarray:
        gaps = [np.diff(self.t_tokens[k]) for k in self.requests(rounds)]
        return np.concatenate(gaps) if gaps else np.zeros(0)

    def tokens(self, rounds=None) -> int:
        return sum(len(self.t_tokens[k]) for k in self.requests(rounds))
