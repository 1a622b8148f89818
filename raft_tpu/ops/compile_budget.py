"""Tier ladder for fused searches, with an opt-in compile budget.

Every fused-search entry runs as a ladder of TIERS, structurally
simplest-last (Pallas auto-lc → Pallas lc=1 → XLA formulation →
probe-major eager scan). Normally the first tier serves, and an error
from it is raised to the caller: a kernel the chip's compiler refuses
is a defect to see, never a query quietly served by another tier.

The ladder's one remaining job is an opt-in timeout. With
``RAFT_TPU_COMPILE_BUDGET_S`` set (default 0 = off on every backend),
the first call of a tier gets that wall-clock budget; a tier that
exceeds it is marked POISONED for the process and the next tier serves
the query. The over-budget compile keeps running in a daemon thread
(a compile cannot be cancelled); if it completes the tier un-poisons,
its executable sitting in the process-wide jit cache. A tier that has
succeeded once runs inline with no thread or budget.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, Sequence, Tuple

from raft_tpu.core.logger import logger

# tier state, process-global: (ladder name, tier name) -> True
_OK: dict = {}
# (ladder name, tier name) -> time.monotonic() when the budget expired
# (monotonic, not wall clock: an NTP step must not stretch or shrink a
# poison window)
_POISONED: dict = {}
_LOCK = threading.Lock()


def budget_s() -> float:
    """Compile budget in seconds; 0 (the default on every backend)
    disables budgeting, so tiers run inline."""
    return float(os.environ.get("RAFT_TPU_COMPILE_BUDGET_S", "0"))


def tier_state(ladder: str, tier: str) -> str:
    """"ok" | "poisoned" | "untried" — introspection for tests/tools."""
    key = (ladder, tier)
    with _LOCK:
        if key in _OK:
            return "ok"
        if key in _POISONED:
            return "poisoned"
    return "untried"


def snapshot() -> dict:
    """``{ladder: {tier: "ok"|"poisoned"}}`` — for tools/logs (the
    bisect ladder prints this so a parked compile is still NAMED even
    though the search it was part of served from a fallback tier)."""
    with _LOCK:
        out: dict = {}
        for (name, tier) in _OK:
            out.setdefault(name, {})[tier] = "ok"
        for (name, tier) in _POISONED:
            out.setdefault(name, {}).setdefault(tier, "poisoned")
    return out


def reset(ladder: Optional[str] = None) -> None:
    """Forget tier state (all ladders, or one) — test/bench helper."""
    with _LOCK:
        for d in (_OK, _POISONED):
            for key in [k for k in d
                        if ladder is None or k[0] == ladder]:
                del d[key]


def _run_inline(name: str, tname: str, thunk: Callable):
    out = thunk()
    with _LOCK:
        _OK[(name, tname)] = True
    return out


def run_tiers(name: str, tiers: Sequence[Tuple[str, Callable]],
              budget: Optional[float] = None):
    """Run the first tier of ``tiers`` that completes within the
    compile budget; an error from a tier is raised, a tier that
    exceeds the budget falls down the ladder.

    ``tiers``: ``[(tier_name, thunk)]`` — each thunk traces, compiles
    (first call) and executes its formulation; order them structurally
    simplest-LAST. The final tier always runs inline (there is nothing
    to fall back to, and parking it would leave the caller with no
    result).
    """
    assert tiers, "run_tiers: empty ladder"
    b = budget_s() if budget is None else budget
    for i, (tname, thunk) in enumerate(tiers):
        key = (name, tname)
        last = i == len(tiers) - 1
        with _LOCK:
            ok = key in _OK
            poisoned = key in _POISONED and not ok
        if poisoned and not last:
            continue
        if b <= 0 or ok or last:
            return _run_inline(name, tname, thunk)
        result: dict = {}
        done = threading.Event()

        def work(thunk=thunk, result=result, done=done, key=key):
            try:
                result["out"] = thunk()
            except BaseException as e:  # noqa: BLE001 - re-raised below
                result["err"] = e
            finally:
                with _LOCK:
                    if "err" not in result:
                        # late completion un-poisons: the executable is
                        # now in the jit cache, future calls are cheap
                        _OK[key] = True
                        _POISONED.pop(key, None)
                done.set()

        t = threading.Thread(target=work, daemon=True,
                             name=f"raft-tpu-compile-{tname}")
        t.start()
        if done.wait(b):
            if "err" in result:
                raise result["err"]
            with _LOCK:
                _OK[key] = True
            return result["out"]
        with _LOCK:
            _POISONED[key] = time.monotonic()
        logger.warn(
            "%s: tier %s exceeded the %.0f s compile budget; compile "
            "parked in a daemon thread, falling back to the next tier",
            name, tname, b)
        # sibling skip: a parked compile indicates backend-family
        # pathology at this shape, and its same-family siblings are
        # near-identical programs — poison them too rather than burn
        # another full budget each
        family = tname.split("_", 1)[0]
        for sib, _ in tiers[i + 1:len(tiers) - 1]:
            if sib.split("_", 1)[0] == family:
                sibkey = (name, sib)
                with _LOCK:
                    if sibkey not in _OK and sibkey not in _POISONED:
                        _POISONED[sibkey] = time.monotonic()
                        logger.warn("%s: tier %s skipped (same-family "
                                    "sibling of the parked %s)",
                                    name, sib, tname)
