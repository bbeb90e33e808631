"""Adaptive codec control plane: pick the wire codec from live signal.

The codec choice used to be static per-config while the PR-3 profiler
already *names* the bound stage every step ("PULL-bound: pull p95 41ms
vs compute 12ms") — the signal existed but nothing acted on it. This
module closes the loop ("Compressed Communication: Adaptive Methods and
System", arxiv 2105.07829: codec choice should follow the measured
bottleneck, not a config flag):

- ``CodecController`` — a PURE hysteresis ladder. Given a per-leaf
  ``CodecPlan`` and a round-stamped ``RoundSignal`` it walks the ladder
  one rung at a time: escalate after ``up_rounds`` consecutive
  PULL-bound rounds, de-escalate after ``down_rounds`` consecutive
  COMPUTE-bound rounds (down > up by default: switching down is cheap to
  defer, switching up under pressure should be prompt). No wall clock,
  no RNG, no global state — two controllers fed identical signal
  sequences emit identical plan sequences, which is the aggregation-
  safety invariant (server folding breaks if workers disagree).
- ``CodecPlane`` — the glue: resolves each eligible leaf's codec at
  ROUND granularity from inside ``PipelineScheduler.submit`` (wire-stage
  entry, not declare time), installs/clears the server-side codec via
  COMP_INIT when a plan switches (only while the leaf's keys are
  quiescent — reconfiguring under an in-flight round would corrupt it),
  and stamps every push with the ``(plan_epoch << 8) | codec_id`` wire
  tag the server validates per round. Cross-worker skew therefore fails
  LOUDLY at the server (codec-tag mismatch → error reply → bounded
  retries → surfaced error), never as a silent mis-fold.

The ladder's default rungs: ``dense`` → ``lossless`` (byte-plane +
entropy tier, ops/compression/lossless.py — bitwise round-trip, so
escalating to it never changes numerics) → ``onebit`` (32x wire
reduction, lossy). Per-leaf plan state lives on the TensorRegistry
(``registry.codec_plan``) so it survives scheduler restarts.

Server-side aggregation stays homomorphic where the codec allows: the
randomk O(k) wire-form sum is untouched, onebit/topk decode-then-fold as
before, and the lossless tier decodes-then-folds with a lossless
recompress of the aggregate (native/ps.cc CompressorCfg LOSSLESS) — the
reply rides the compressed wire too.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Dict, List, Optional, Tuple

from ..utils.logging import log

# Wire codec ids (MsgHeader::codec low byte) — values are wire contract,
# mirrored by native/ps.cc enum WireCodec. 0 = untagged.
WIRE_CODEC_IDS = {
    "dense": 1,
    "lossless": 2,
    "onebit": 3,
    "topk": 4,
    "randomk": 5,
    "dithering": 6,
}

# kwargs each ladder rung installs server-side (the dense rung installs
# the explicit CLEAR so de-escalated keys pass the server's mode gate)
_TIER_KWARGS = {
    "lossless": {"compressor": "lossless"},
    "onebit": {"compressor": "onebit"},
    "topk": {"compressor": "topk", "k": "0.01"},
    "randomk": {"compressor": "randomk", "k": "0.01"},
    "dithering": {"compressor": "dithering"},
}

_DEFAULT_LADDER = ("dense", "lossless", "onebit")

# rungs that change numerics — capped away from fused buckets (below)
_LOSSY_TIERS = frozenset(("onebit", "topk", "randomk", "dithering"))


@dataclasses.dataclass
class RoundSignal:
    """One round boundary's deterministic inputs: the step ordinal,
    the stage walls the diagnosis compares (core/metrics.py
    classify_step, milliseconds), and the training-health verdict
    (``degraded`` = the HealthPlane detector flagged an anomaly this
    step — core/health.py). The nonfinite/explode/collapse inputs are
    post-aggregation statistics (identical on every worker); the
    drift class is additionally control-RPC-dependent — either way
    the veto is exactly as skew-safe as the perf signal that already
    drives this plane: quiescent-boundary application + the server's
    loud codec-tag gate."""

    step: int
    compute_ms: float
    pull_ms: float  # max(pull p95, aggregate drain pull-wait)
    degraded: bool = False

    @classmethod
    def from_report(cls, r) -> "RoundSignal":
        return cls(step=r.step, compute_ms=r.compute_ms or 0.0,
                   pull_ms=max(r.pull_p95_ms or 0.0, r.pull_wait_ms or 0.0),
                   degraded=bool(getattr(r, "health_flags", None)))


@dataclasses.dataclass
class CodecPlan:
    """Per-leaf plan state (held by the TensorRegistry): the active
    rung, the plan epoch (bumped on every applied switch — part of the
    wire tag, so epoch skew across workers is as loud as codec skew),
    and the hysteresis streaks."""

    rung: int = 0
    epoch: int = 0
    up_streak: int = 0
    down_streak: int = 0
    # what the SERVER currently has installed for this leaf (None =
    # nothing ever installed = dense store default); the plane converges
    # applied -> desired only while the leaf's keys are quiescent
    applied: Optional[str] = None


class CodecController:
    """Pure deterministic ladder walker — see module docstring."""

    def __init__(self, ladder=_DEFAULT_LADDER, up_rounds: int = 3,
                 down_rounds: int = 8, pull_ratio: float = 1.5):
        if not ladder:
            raise ValueError("codec ladder must name at least one tier")
        for t in ladder:
            if t != "dense" and t not in _TIER_KWARGS:
                raise ValueError(f"unknown codec ladder tier {t!r}")
        self.ladder: Tuple[str, ...] = tuple(ladder)
        self.up_rounds = max(1, int(up_rounds))
        self.down_rounds = max(1, int(down_rounds))
        self.pull_ratio = float(pull_ratio)

    def pull_bound(self, sig: RoundSignal) -> bool:
        """The escalation predicate: the wire must dominate compute by
        the configured ratio (a strict classify_step tie is not enough —
        a 1.01x 'PULL-bound' verdict would thrash the ladder)."""
        return sig.pull_ms > self.pull_ratio * max(sig.compute_ms, 1e-9)

    def safe_rung(self, rung: int) -> Optional[int]:
        """The highest numerics-safe (non-lossy) rung at or below
        ``rung`` — where the health veto de-escalates to: ``lossless``
        when the ladder carries it (bitwise round-trip, so it keeps
        the wire win), else ``dense``. None when the operator built an
        all-lossy ladder: there is nowhere safe to go, so the veto can
        only hold (escalation stays blocked) rather than thrash."""
        for i in range(min(rung, len(self.ladder) - 1), -1, -1):
            if self.ladder[i] not in _LOSSY_TIERS:
                return i
        return None

    def decide(self, plan: CodecPlan, sig: RoundSignal) -> Optional[str]:
        """Advance ``plan``'s streaks with one round's signal; returns
        the tier to switch to, or None to hold. Deterministic: a pure
        function of (plan state, signal).

        The numerics veto (core/health.py): a ``degraded`` signal can
        NEVER escalate — and when the plan sits on a lossy rung it
        de-escalates immediately (no down-streak wait) to the highest
        numerics-safe rung, jumping rungs if it must. Perf pressure
        resumes walking the ladder only after the health plane reads
        healthy again — convergence outranks wire bytes."""
        if sig.degraded:
            plan.up_streak = 0
            plan.down_streak = 0
            if self.ladder[plan.rung] in _LOSSY_TIERS:
                safe = self.safe_rung(plan.rung)
                # no safe rung below (all-lossy ladder) or already
                # there: hold — returning the same tier every degraded
                # round would read as a switch per round and spam the
                # apply path without changing anything
                if safe is not None and safe != plan.rung:
                    plan.rung = safe
                    return self.ladder[safe]
            return None
        if self.pull_bound(sig):
            plan.up_streak += 1
            plan.down_streak = 0
            if (plan.up_streak >= self.up_rounds
                    and plan.rung + 1 < len(self.ladder)):
                plan.rung += 1
                plan.up_streak = 0
                return self.ladder[plan.rung]
            return None
        plan.down_streak += 1
        plan.up_streak = 0
        if plan.down_streak >= self.down_rounds and plan.rung > 0:
            plan.rung -= 1
            plan.down_streak = 0
            return self.ladder[plan.rung]
        return None


def register_codec_metrics(metrics) -> None:
    """Create the codec plane's instruments eagerly so the
    docs/observability.md schema resolves them on every deployment,
    adaptive or not (the same contract as the wire/retries family)."""
    metrics.counter("codec/switches")
    metrics.counter("codec/health_vetoes")
    metrics.counter("codec/lossless_bytes_pre")
    metrics.counter("codec/lossless_bytes_post")
    for tier in ("dense", "lossless", "onebit", "randomk"):
        metrics.gauge(f"codec/active/{tier}")
    metrics.gauge("codec/lossless_ratio")


class CodecPlane:
    """Round-granular codec resolution for the pipeline scheduler.

    ``resolve(ctx, flat)`` is called by ``PipelineScheduler.submit`` for
    every tensor whose caller did not choose a codec explicitly; it
    returns ``(comp, tag_comp, tag_dense)`` — the CompressedTensor to
    splice into the COMPRESS/DECOMPRESS stages (or None for dense) and
    the wire tags for compressed resp. dense partitions of this round.
    """

    def __init__(self, client, registry, metrics, profiler, num_workers,
                 scheduler=None, config=None):
        def env(name, default):
            return os.environ.get(name, default)

        self._client = client
        self._registry = registry
        self._profiler = profiler
        self._num_workers = max(1, int(num_workers))
        self._scheduler = scheduler
        ladder = tuple(
            t.strip() for t in
            env("BYTEPS_CODEC_LADDER", ",".join(_DEFAULT_LADDER)).split(",")
            if t.strip())
        self._controller = CodecController(
            ladder=ladder,
            up_rounds=int(env("BYTEPS_CODEC_UP_ROUNDS", "3")),
            down_rounds=int(env("BYTEPS_CODEC_DOWN_ROUNDS", "8")),
            pull_ratio=float(env("BYTEPS_CODEC_PULL_RATIO", "1.5")))
        pin = env("BYTEPS_CODEC_PIN", "").strip()
        if pin and pin != "dense" and pin not in _TIER_KWARGS:
            raise ValueError(f"BYTEPS_CODEC_PIN={pin!r} is not a tier")
        self._pin = pin or None
        self._min_bytes = int(env("BYTEPS_CODEC_MIN_BYTES", "65536"))
        self._mu = threading.Lock()
        self._ingest_mu = threading.Lock()  # one-shot report ingestion
        # (name, tier) -> CompressedTensor (codec stacks persist across
        # re-escalations so randomk seeds / step counters stay stable)
        self._tensors: Dict[tuple, object] = {}  # guarded-by: _mu
        self._adaptive_names: set = set()        # guarded-by: _mu
        self._last_signal_step = 0         # guarded-by: _ingest_mu
        self._metrics = metrics
        if metrics is not None:
            register_codec_metrics(metrics)
            self._m_switches = metrics.counter("codec/switches")
            self._m_vetoes = metrics.counter("codec/health_vetoes")
            pre = metrics.counter("codec/lossless_bytes_pre")
            post = metrics.counter("codec/lossless_bytes_post")
            metrics.gauge("codec/lossless_ratio").set_fn(
                lambda: (post.value / pre.value) if pre.value else 0.0)
            for tier in ("dense", "lossless", "onebit", "randomk"):
                metrics.gauge(f"codec/active/{tier}").set_fn(
                    lambda t=tier: self._active_count(t))
        else:
            self._m_switches = None
            self._m_vetoes = None

    # ------------------------------------------------------------------ #
    # signal intake
    # ------------------------------------------------------------------ #

    def observe(self, sig: RoundSignal) -> List[Tuple[str, str]]:
        """Feed one round signal to every adaptive leaf's plan; returns
        the (name, new_tier) switches DECIDED (they are applied lazily,
        at each leaf's next quiescent resolve). Exposed for tests and
        for drivers with out-of-band signals; the scheduler path feeds
        it automatically from the StepReport ring."""
        switched = []
        vetoed = False
        with self._mu:
            for name in sorted(self._adaptive_names):
                plan = self._registry.codec_plan(name)
                on_lossy = self._controller.ladder[plan.rung] \
                    in _LOSSY_TIERS
                tier = self._controller.decide(plan, sig)
                if sig.degraded and (on_lossy or tier is None):
                    vetoed = True
                if tier is not None:
                    switched.append((name, tier))
        if vetoed:
            # the numerics veto engaged: escalation suppressed and/or
            # lossy rungs forced down — the first consumer of a
            # training-health signal (docs/compression.md)
            if self._m_vetoes is not None:
                self._m_vetoes.inc()
            from . import flight
            flight.record(
                "codec_health_veto", key=sig.step,
                detail=f"health-degraded signal at step {sig.step}: "
                       f"escalation vetoed"
                       + (f"; forced de-escalation of "
                          f"{len(switched)} leaves"
                          if switched else ""))
        return switched

    def _ingest_reports(self) -> None:
        """Pull any StepReports newer than the last-seen step out of the
        profiler ring and run the controller over them — the lazy round-
        boundary hook (resolve() runs at every round's submit). The
        ingest lock makes each report feed the controller EXACTLY once:
        concurrent resolves racing here would double-advance the hysteresis
        streaks and de-synchronize plans across workers."""
        if self._profiler is None:
            return
        with self._ingest_mu:
            reports = [r for r in self._profiler.reports()
                       if r.step > self._last_signal_step]
            for r in reports:
                self._last_signal_step = r.step
                for name, tier in self.observe(RoundSignal.from_report(r)):
                    log.info("codec plane: leaf %r -> %s (%s)", name,
                             tier, classify_msg(r))

    # ------------------------------------------------------------------ #
    # per-round resolution
    # ------------------------------------------------------------------ #

    def eligible(self, ctx, flat) -> bool:
        import numpy as np
        return (flat.dtype == np.float32
                and flat.nbytes >= self._min_bytes
                and ctx.partitions is not None and len(ctx.partitions) > 0)

    def resolve(self, ctx, flat):
        """Resolve ``ctx``'s codec for THIS round. Returns
        ``(comp, tag_comp, tag_dense)``; ``comp`` is None for the dense
        tier. Must be called before the round's tasks are enqueued."""
        if not self.eligible(ctx, flat):
            return None, 0, 0
        self._ingest_reports()
        with self._mu:
            self._adaptive_names.add(ctx.name)
            plan = self._registry.codec_plan(ctx.name)
            if self._pin is not None:
                # operator override: the ladder is bypassed but the wire
                # tag (and COMP_INIT convergence) still applies
                desired = self._pin
                plan.rung = (self._controller.ladder.index(desired)
                             if desired in self._controller.ladder else 0)
            else:
                desired = self._controller.ladder[plan.rung]
            # fused buckets concatenate sub-min-compress leaves (biases,
            # norms) that the explicit-compression gate deliberately
            # keeps full-precision (jax/train.py interaction rules); the
            # plane honors the same intent — a lossy rung never governs
            # a `fused/` key, the bitwise lossless tier may
            if desired in _LOSSY_TIERS and ctx.name.startswith("fused/"):
                desired = ("lossless"
                           if "lossless" in self._controller.ladder
                           else "dense")
            applied = plan.applied if plan.applied is not None else "dense"
            if desired != applied:
                if self._keys_quiescent(ctx):
                    self._apply_locked(ctx, plan, desired)
                    applied = desired
                # else: keep folding with the applied tier this round;
                # the switch lands at the next quiescent boundary
            comp = None
            if applied != "dense":
                comp = self._tensor_locked(ctx, applied)
            tag_comp = (plan.epoch & 0xFFFFFF) << 8 | WIRE_CODEC_IDS.get(
                applied, 1)
            tag_dense = (plan.epoch & 0xFFFFFF) << 8 | WIRE_CODEC_IDS[
                "dense"]
            return comp, tag_comp, tag_dense

    def plan_snapshot(self) -> Dict[str, dict]:
        """name -> {tier, epoch, rung} for telemetry / tests."""
        with self._mu:
            out = {}
            for name in sorted(self._adaptive_names):
                plan = self._registry.codec_plan(name)
                out[name] = {
                    "tier": plan.applied or "dense",
                    "epoch": plan.epoch,
                    "rung": plan.rung,
                }
            return out

    # ------------------------------------------------------------------ #
    # internals
    # ------------------------------------------------------------------ #

    def _active_count(self, tier: str) -> int:
        with self._mu:
            n = 0
            for name in self._adaptive_names:
                plan = self._registry.codec_plan(name)
                if (plan.applied or "dense") == tier:
                    n += 1
            return n

    def _keys_quiescent(self, ctx) -> bool:
        if self._scheduler is None:
            return True
        idle = getattr(self._scheduler, "keys_idle", None)
        if idle is None:
            return True
        return idle([p.key for p in ctx.partitions])

    def _tensor_locked(self, ctx, tier):  # caller-holds: _mu
        ct = self._tensors.get((ctx.name, tier))
        if ct is not None and (ct.ctx is not ctx
                               or len(ct.stacks) != len(ctx.partitions)):
            # the leaf was re-declared/re-partitioned: stale per-
            # partition stacks would compress the wrong byte ranges
            ct = None
        if ct is None:
            from ..server.compressed import CompressedTensor
            ct = CompressedTensor(
                self._client, ctx, dict(_TIER_KWARGS[tier]),
                self._num_workers, min_compress_bytes=0)
            self._tensors[(ctx.name, tier)] = ct
        return ct

    # caller-holds: _mu
    def _apply_locked(self, ctx, plan: CodecPlan, tier: str) -> None:
        """Install ``tier``'s server-side codec for every partition of
        ``ctx`` (COMP_INIT; ``compressor=none`` clears for dense) and
        bump the plan epoch. Caller holds the plane lock and has
        verified the keys are quiescent, so no in-flight round can race
        the server-side reset."""
        nbytes = sum(p.length for p in ctx.partitions)
        self._client.ensure_init(ctx, nbytes)
        ct = None if tier == "dense" else self._tensor_locked(ctx, tier)
        for i, p in enumerate(ctx.partitions):
            stack = ct.stacks[i] if ct is not None else None
            kwargs = (stack.kwargs_wire() if stack is not None
                      else f"compressor=none;n={p.length // 4}")
            self._client.comp_init(p.server, p.key, kwargs)
        if ct is not None:
            # the plane just installed the server-side codecs; the
            # CompressedTensor must not re-install (its _install would
            # be a redundant-but-idempotent re-send)
            ct._installed = True
        prev = plan.applied or "dense"
        plan.applied = tier
        plan.epoch += 1
        if self._m_switches is not None:
            self._m_switches.inc()
        from . import flight
        flight.record("codec_switch", key=ctx.declared_key,
                      detail=f"{ctx.name} {prev}->{tier} "
                             f"epoch={plan.epoch}")
        log.info("codec plane: %r %s -> %s (plan epoch %d)",
                 ctx.name, prev, tier, plan.epoch)


def classify_msg(report) -> str:
    from .metrics import classify_step
    try:
        return classify_step(report)
    except Exception:  # noqa: BLE001 - diagnosis is advisory
        return "?"
