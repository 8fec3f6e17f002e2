"""Runtime lock-order witness: deadlock cycles and held-across-blocking.

Env-gated by `PILOSA_TPU_LOCKCHECK=1` (install() patches the
`threading.Lock` / `threading.RLock` factories; without it every path in
this module is a no-op and production code pays nothing beyond one module
attribute load at the RPC/dispatch choke points).

What it records, per witnessed lock *construction site* (file:line — the
stable identity across instances):

* the cross-thread acquisition graph: acquiring B while holding A adds
  the edge A→B, remembered with the stack that first formed it. An edge
  that closes a cycle (B can already reach A) is a potential deadlock —
  two threads interleaving those paths can block forever — reported with
  both stacks. Self-edges (two instances from one site, e.g. two
  fragments) are tracked separately as info, not violations.
* held-across-blocking: the RPC and device-dispatch choke points
  (InternalClient._request, telemetry.counted_jit / record_dispatch,
  mesh put paths) call `note_blocking(kind, detail)`; if the calling
  thread holds any witnessed lock at that moment, the violation is
  recorded with the held sites and the offending stack. A lock held
  across a network round trip or an XLA dispatch serializes every
  sibling of that lock behind a peer or a device — the no-lock-across-
  dispatch discipline the executor/batcher/residency layers maintain.

Only locks *constructed from pilosa_tpu (or tests) frames* are wrapped;
stdlib/jax-internal locks stay native, keeping overhead proportional to
our own locking. Condition/Event over witnessed locks work: the RLock
wrapper implements the `_release_save`/`_acquire_restore`/`_is_owned`
protocol, the Lock wrapper lets Condition fall back to acquire/release.

Tier-1 runs with the witness enabled (tests/conftest.py) and asserts a
clean report per test, so every concurrency test doubles as a race
regression test. Runbook: docs/operations.md "Static analysis and race
detection".
"""

from __future__ import annotations

import os
import sys
import threading
import traceback
from typing import Optional

ENV_GATE = "PILOSA_TPU_LOCKCHECK"

_real_lock = threading.Lock
_real_rlock = threading.RLock

# frames from these files never count as a lock's construction site
_SELF_FILE = os.path.abspath(__file__)
_THREADING_FILE = getattr(threading, "__file__", "<threading>")


def _call_site() -> Optional[str]:
    """file:line of the first frame outside this module and threading.py,
    or None when that frame is not pilosa_tpu/tests code (the caller gets
    a native lock)."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        if fn != _SELF_FILE and not fn.startswith(_THREADING_FILE):
            if "pilosa_tpu" in fn or f"{os.sep}tests{os.sep}" in fn:
                short = fn
                for marker in ("pilosa_tpu", "tests"):
                    i = fn.rfind(marker)
                    if i >= 0:
                        short = fn[i:]
                        break
                return f"{short}:{f.f_lineno}"
            return None
        f = f.f_back
    return None


def _stack(_ignored: int = 0) -> str:
    """Formatted stack starting at the first frame outside this module —
    the choke point / lock-acquire site that triggered the recording."""
    f = sys._getframe(1)
    while f is not None and f.f_code.co_filename == _SELF_FILE:
        f = f.f_back
    return "".join(traceback.format_stack(f, limit=16))


class Witness:
    """One acquisition-graph recorder. The module-level singleton backs
    the env gate; tests may construct private instances."""

    def __init__(self):
        self._mu = _real_lock()          # leaf lock: guards everything below
        self._adj: dict[str, set] = {}   # site -> reachable-next sites
        self._edge_stacks: dict = {}     # (a, b) -> stack that formed a→b
        self._tls = threading.local()
        self.cycles: list[dict] = []
        self.blocking: list[dict] = []
        self.self_edges: set = set()     # info, not violations
        self._seen_cycles: set = set()
        self._seen_blocking: set = set()

    # -- per-thread held stack --------------------------------------------

    def _held(self) -> list:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def violation_count(self) -> int:
        with self._mu:
            return len(self.cycles) + len(self.blocking)

    # -- recording ---------------------------------------------------------

    def note_acquired(self, lock: "_WitnessLockBase") -> None:
        held = self._held()
        for site, obj_id, count in reversed(held):
            if obj_id == id(lock):       # reentrant re-acquire
                held[held.index((site, obj_id, count))] = (
                    site, obj_id, count + 1)
                return
        if held and lock.site is not None:
            self._record_edges([s for s, _, _ in held], lock.site)
        held.append((lock.site, id(lock), 1))

    def note_released(self, lock: "_WitnessLockBase") -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            site, obj_id, count = held[i]
            if obj_id == id(lock):
                if count > 1:
                    held[i] = (site, obj_id, count - 1)
                else:
                    del held[i]
                return

    def drop_all(self, lock: "_WitnessLockBase") -> int:
        """Remove every held entry for `lock` (Condition _release_save);
        returns the reentrancy count to restore later."""
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            site, obj_id, count = held[i]
            if obj_id == id(lock):
                del held[i]
                return count
        return 1

    def restore(self, lock: "_WitnessLockBase", count: int) -> None:
        self._held().append((lock.site, id(lock), count))

    def _record_edges(self, held_sites: list, new_site: str) -> None:
        stack = None
        with self._mu:
            for a in held_sites:
                if a is None or a == new_site:
                    if a == new_site:
                        self.self_edges.add(a)
                    continue
                if new_site in self._adj.setdefault(a, set()):
                    continue
                self._adj[a].add(new_site)
                if stack is None:
                    stack = _stack(4)
                self._edge_stacks[(a, new_site)] = stack
                path = self._find_path(new_site, a)
                if path is not None:
                    cyc = tuple(sorted(set(path + [new_site])))
                    if cyc not in self._seen_cycles:
                        self._seen_cycles.add(cyc)
                        self.cycles.append({
                            "cycle": path + [new_site],
                            "newEdge": (a, new_site),
                            "newEdgeStack": stack,
                            "priorStacks": {
                                f"{x}->{y}": self._edge_stacks.get((x, y))
                                for x, y in zip(path, path[1:])},
                        })

    def _find_path(self, src: str, dst: str) -> Optional[list]:
        """DFS path src..dst in the site graph, else None."""
        seen = {src}
        stack = [(src, [src])]
        while stack:
            node, path = stack.pop()
            for nxt in self._adj.get(node, ()):
                if nxt == dst:
                    return path + [dst]
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append((nxt, path + [nxt]))
        return None

    def note_blocking(self, kind: str, detail: str = "") -> None:
        held = self._held()
        if not held:
            return
        sites = tuple(s for s, _, _ in held if s is not None)
        if not sites:
            return
        key = (kind, sites)
        with self._mu:
            if key in self._seen_blocking:
                return
            self._seen_blocking.add(key)
            self.blocking.append({
                "kind": kind, "detail": detail, "held": list(sites),
                "stack": _stack(3),
            })

    # -- reporting ---------------------------------------------------------

    def report(self) -> dict:
        with self._mu:
            return {
                "cycles": list(self.cycles),
                "heldAcrossBlocking": list(self.blocking),
                "selfEdges": sorted(self.self_edges),
                "edges": sum(len(v) for v in self._adj.values()),
            }

    def format_violations(self, cycles=None, blocking=None) -> str:
        with self._mu:
            cycles = list(self.cycles) if cycles is None else cycles
            blocking = list(self.blocking) if blocking is None else blocking
        out = []
        for c in cycles:
            out.append("LOCK-ORDER CYCLE: " + " -> ".join(c["cycle"]))
            out.append(f"closing edge {c['newEdge'][0]} -> "
                       f"{c['newEdge'][1]} formed at:\n{c['newEdgeStack']}")
            for edge, stk in (c.get("priorStacks") or {}).items():
                if stk:
                    out.append(f"prior edge {edge} formed at:\n{stk}")
        for b in blocking:
            out.append(
                f"LOCK HELD ACROSS {b['kind'].upper()}"
                f" ({b['detail']}): held={b['held']}\n{b['stack']}")
        return "\n".join(out) or "clean"


# ---------------------------------------------------------------------------
# Lock wrappers
# ---------------------------------------------------------------------------


class _WitnessLockBase:
    __slots__ = ("_inner", "site", "_w")

    def __init__(self, inner, site: Optional[str], witness: "Witness"):
        self._inner = inner
        self.site = site
        self._w = witness

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._w.note_acquired(self)
        return ok

    def release(self) -> None:
        self._inner.release()
        self._w.note_released(self)

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def _at_fork_reinit(self) -> None:
        self._inner._at_fork_reinit()

    def __repr__(self) -> str:
        return f"<{type(self).__name__} site={self.site}>"


class WitnessLock(_WitnessLockBase):
    """threading.Lock wrapper. Condition over it falls back to plain
    acquire/release (no _release_save here), which keeps bookkeeping."""
    __slots__ = ()


class WitnessRLock(_WitnessLockBase):
    """threading.RLock wrapper, incl. the Condition integration hooks."""
    __slots__ = ()

    def _is_owned(self) -> bool:
        return self._inner._is_owned()

    def _release_save(self):
        state = self._inner._release_save()
        count = self._w.drop_all(self)
        return (state, count)

    def _acquire_restore(self, saved) -> None:
        state, count = saved
        self._inner._acquire_restore(state)
        self._w.restore(self, count)


# ---------------------------------------------------------------------------
# Global install
# ---------------------------------------------------------------------------

_GLOBAL = Witness()
ACTIVE = False


def _make_lock():
    site = _call_site()
    inner = _real_lock()
    return WitnessLock(inner, site, _GLOBAL) if site is not None else inner


def _make_rlock():
    site = _call_site()
    inner = _real_rlock()
    return WitnessRLock(inner, site, _GLOBAL) if site is not None else inner


def native_rlock():
    """A reentrant lock the witness never tracks: for a lock held across
    a dispatch by design (utils/telemetry.py's launch order on the CPU
    backend), which the witness would otherwise report at every launch."""
    return _real_rlock()


def install() -> None:
    """Patch the threading lock factories; idempotent."""
    global ACTIVE
    if ACTIVE:
        return
    threading.Lock = _make_lock
    threading.RLock = _make_rlock
    ACTIVE = True


def uninstall() -> None:
    """Restore the native factories. Locks already wrapped keep working
    (and keep recording) — only new constructions revert."""
    global ACTIVE
    threading.Lock = _real_lock
    threading.RLock = _real_rlock
    ACTIVE = False


def maybe_install() -> bool:
    if os.environ.get(ENV_GATE, "") == "1":
        install()
    return ACTIVE


def note_blocking(kind: str, detail: str = "") -> None:
    """Choke-point hook: a witnessed lock held here is a violation.
    No-op (one attribute load + branch) unless the witness is active."""
    if ACTIVE:
        _GLOBAL.note_blocking(kind, detail)


def report() -> dict:
    return _GLOBAL.report()


def violation_count() -> int:
    return _GLOBAL.violation_count()


def format_violations() -> str:
    return _GLOBAL.format_violations()
