"""Deterministic fault-injection plane (ISSUE 15).

One seedable, per-container fault plan that the chaos tests drive
instead of hand-rolling one-off ``FaultyEngine`` subclasses (the
ISSUE 14 e2e pattern, promoted to a first-class plane). Production
processes opt in via env::

    TPU9_FAULTS="crash:after_tokens=8,flag=1;rpc_error:times=2,prob=0.5"
    TPU9_FAULTS_SEED=42
    TPU9_FAULTS_FLAG_DIR=/tmp/chaos        # for flag-armed faults

Spec grammar: ``kind:opt=val,opt=val;kind:...``. Options (all optional):

- ``after_tokens=N``   — arm once the hooked counter (engine
  ``tokens_generated``) reaches N
- ``after_calls=N``    — arm from the Nth ``fire()`` call (1-based)
- ``times=K``          — fire at most K times (default: crash/proc_exit
  fire once, everything else unbounded)
- ``prob=P``           — fire with probability P per armed call, drawn
  from the plane's seeded RNG (default 1.0)
- ``delay_s=S``        — for slowness faults: injected latency
- ``duration_s=S``     — for window faults (stall, heartbeat_loss):
  active for S seconds from first arming, then auto-clears (recovery)
- ``flag=1``           — additionally require the per-container flag
  file ``<TPU9_FAULTS_FLAG_DIR>/<kind>-<container_id>`` to exist; this
  is how a multi-replica e2e picks its victim at runtime

Fault kinds and their hook points:

==================  ========================================================
``crash``           engine serve-loop raises at the next window dispatch
                    (runner: :meth:`FaultPlane.instrument_engine`)
``stall``           window dispatch spins without progress while the event
                    loop (and so the pressure heartbeat) stays alive — the
                    ISSUE 14 gray failure
``proc_exit``       hard replica death: ``os._exit`` mid token stream
                    (runner SSE write loop)
``heartbeat_loss``  runner skips pressure beats while active
``rpc_error``       runner aborts the inbound RPC transport (the gateway
                    sees a mid-request connection reset)
``peer_read_error`` cache peer chunk read raises (hedged-read path)
``peer_read_slow``  cache peer chunk read delayed by ``delay_s``
``tree_peer_loss``  scale-out tree (ISSUE 17): reads against ONE peer —
                    selected with the ``peer=<addr substring>`` option —
                    fail from arming on, simulating a tree parent dying
                    mid-transfer; the hedged read re-plans onto the
                    surviving preference list (cache ``_peer_get`` via
                    :meth:`FaultPlane.fire_peer`)
``kv_ship_error``   runner's kvwire adopt path fails before the fetch —
                    block-ship resume degrades to re-prefill (ISSUE 16)
==================  ========================================================

The plane is **deliberately dependency-free** (no imports from
tpu9.serving/gateway/router): engine hooks patch the *instance* it is
handed. ``boundaries.toml`` restricts importers to the runner/worker/
cache hook sites, tests and bench — the BND001 cross-check test asserts
this module stays out of every other production import path.
"""

from __future__ import annotations

import logging
import os
import random
import time
from dataclasses import dataclass, field
from typing import Optional

log = logging.getLogger("tpu9.faults")

ENV_SPEC = "TPU9_FAULTS"
ENV_SEED = "TPU9_FAULTS_SEED"
ENV_FLAG_DIR = "TPU9_FAULTS_FLAG_DIR"

# kinds that default to firing exactly once (terminal by nature)
_ONESHOT_KINDS = ("crash", "proc_exit")


@dataclass
class FaultSpec:
    kind: str
    after_tokens: int = 0
    after_calls: int = 0
    times: int = 0                 # 0 = kind default (oneshot or unbounded)
    prob: float = 1.0
    delay_s: float = 0.0
    duration_s: float = 0.0
    flag: bool = False
    # runtime state
    fired: int = 0
    calls: int = 0
    armed_at: float = 0.0          # monotonic stamp of first arming
    extra: dict = field(default_factory=dict)

    @property
    def max_times(self) -> int:
        if self.times > 0:
            return self.times
        return 1 if self.kind in _ONESHOT_KINDS else 0


def parse_spec(raw: str) -> dict[str, FaultSpec]:
    """``kind:opt=val,...;kind:...`` → specs by kind. Unknown options are
    kept in ``extra`` (forward-compatible) but unknown *grammar* fails
    loudly — a typo'd fault plan silently injecting nothing would be the
    worst kind of chaos test."""
    specs: dict[str, FaultSpec] = {}
    for part in (p.strip() for p in raw.split(";")):
        if not part:
            continue
        kind, _, opts = part.partition(":")
        kind = kind.strip()
        if not kind:
            raise ValueError(f"fault spec entry has no kind: {part!r}")
        spec = FaultSpec(kind=kind)
        for opt in (o.strip() for o in opts.split(",") if o.strip()):
            key, sep, val = opt.partition("=")
            if not sep:
                raise ValueError(
                    f"fault option {opt!r} (in {part!r}) is not key=value")
            key = key.strip()
            if key in ("after_tokens", "after_calls", "times"):
                setattr(spec, key, int(val))
            elif key in ("prob", "delay_s", "duration_s"):
                setattr(spec, key, float(val))
            elif key == "flag":
                spec.flag = val.strip() not in ("", "0", "false")
            else:
                spec.extra[key] = val
        specs[kind] = spec
    return specs


class FaultPlane:
    """Deterministic per-process fault decisions. All decisions flow
    through :meth:`fire`/:meth:`active` so counts stay auditable in
    :meth:`snapshot` (bench and the e2e asserts read it)."""

    def __init__(self, specs: dict[str, FaultSpec], seed: int = 0,
                 container_id: str = "", flag_dir: str = ""):
        self.specs = specs
        self.seed = seed
        self.container_id = container_id
        self.flag_dir = flag_dir
        # one RNG per kind, derived from the seed: firing order of one
        # fault kind never perturbs another's schedule
        self._rngs = {k: random.Random(f"{seed}:{k}")
                      for k in specs}

    # -- construction --------------------------------------------------------

    @classmethod
    def from_env(cls, environ=None) -> Optional["FaultPlane"]:
        env = environ if environ is not None else os.environ
        raw = env.get(ENV_SPEC, "")
        if not raw:
            return None
        return cls(parse_spec(raw),
                   seed=int(env.get(ENV_SEED, "0") or 0),
                   container_id=env.get("TPU9_CONTAINER_ID", ""),
                   flag_dir=env.get(ENV_FLAG_DIR, ""))

    # -- decision core -------------------------------------------------------

    def _flag_ok(self, spec: FaultSpec) -> bool:
        if not spec.flag:
            return True
        if not self.flag_dir:
            return False
        return os.path.exists(os.path.join(
            self.flag_dir, f"{spec.kind}-{self.container_id}"))

    def _armed(self, spec: FaultSpec, tokens: Optional[int]) -> bool:
        if spec.after_tokens and (tokens is None
                                  or tokens < spec.after_tokens):
            return False
        if spec.after_calls and spec.calls < spec.after_calls:
            return False
        return self._flag_ok(spec)

    def fire(self, kind: str, tokens: Optional[int] = None) -> bool:
        """One deterministic should-this-fault-fire-now decision.
        ``tokens`` is the hook's progress counter for ``after_tokens``
        triggers (engine tokens_generated, stream watermark, ...)."""
        spec = self.specs.get(kind)
        if spec is None:
            return False
        spec.calls += 1
        if not self._armed(spec, tokens):
            return False
        if spec.max_times and spec.fired >= spec.max_times:
            return False
        if spec.prob < 1.0 and self._rngs[kind].random() >= spec.prob:
            return False
        spec.fired += 1
        log.warning("fault plane: firing %r (fired %d, call %d)",
                    kind, spec.fired, spec.calls)
        return True

    def fire_peer(self, kind: str, peer: str,
                  tokens: Optional[int] = None) -> bool:
        """Peer-targeted faults (``tree_peer_loss``): fire only when the
        spec's ``peer=`` option (substring match on the address, empty =
        any peer) selects this peer. Calls against non-matching peers do
        NOT advance the spec's call counter — ``after_calls=N`` counts
        attempts against the victim, which is what "dies after N chunks"
        means in a multi-peer race."""
        spec = self.specs.get(kind)
        if spec is None:
            return False
        pat = str(spec.extra.get("peer", ""))
        if pat and pat not in peer:
            return False
        return self.fire(kind, tokens=tokens)

    def active(self, kind: str, tokens: Optional[int] = None) -> bool:
        """Window faults (stall / heartbeat_loss): True while the fault
        holds. First armed observation stamps the window; with
        ``duration_s`` set the window auto-clears — that expiry IS the
        recovery the failover e2e measures."""
        spec = self.specs.get(kind)
        if spec is None:
            return False
        spec.calls += 1
        if not self._armed(spec, tokens):
            return False
        now = time.monotonic()
        if spec.armed_at == 0.0:
            spec.armed_at = now
            spec.fired += 1
            log.warning("fault plane: %r window opened", kind)
        if spec.duration_s > 0 and now - spec.armed_at > spec.duration_s:
            return False
        return True

    def delay_s(self, kind: str) -> float:
        """Injected latency for slowness faults; 0.0 when the fault does
        not fire (counts through :meth:`fire` so prob/times apply)."""
        spec = self.specs.get(kind)
        if spec is None or spec.delay_s <= 0:
            return 0.0
        return spec.delay_s if self.fire(kind) else 0.0

    def snapshot(self) -> dict:
        """Fired/call counts per kind — the audit trail bench and the
        e2e chaos run assert against."""
        return {k: {"fired": s.fired, "calls": s.calls}
                for k, s in self.specs.items()}

    # -- engine instrumentation ---------------------------------------------

    def instrument_engine(self, engine):
        """Patch serve-loop fault hooks onto an engine INSTANCE (no
        serving import — the plane only touches what it is handed):
        ``crash`` raises at the next window dispatch, ``stall`` spins
        dispatch without progress while the runner's event loop (and so
        its heartbeat) stays alive. Returns the same engine."""
        if not any(k in self.specs for k in ("crash", "stall")):
            return engine
        orig_dispatch = engine._dispatch_window
        plane = self

        def faulty_dispatch():
            tokens = engine._stats.get("tokens_generated", 0)
            if plane.fire("crash", tokens=tokens):
                raise RuntimeError(
                    "tpu9.testing.faults: induced engine crash "
                    f"(tokens_generated={tokens})")
            if plane.active("stall", tokens=tokens):
                # cheap blocking spin: the serve loop's own sleep(0)
                # still yields between dispatches, so heartbeats keep
                # flowing — a gray failure, not a dead process
                time.sleep(0.02)
                return None
            return orig_dispatch()

        engine._dispatch_window = faulty_dispatch
        log.warning("fault plane: engine instrumented (%s)",
                    sorted(self.specs))
        return engine
