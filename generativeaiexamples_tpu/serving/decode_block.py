"""How many steps the next decode block runs: the scheduler's ONE
choice of K (`engine.py::_dispatch_decode` asks `choose_k`), and the
step time it is made with.

A block is the unit an arrival waits in: a freed slot's next occupant
stands behind the rest of the running block, the block queued behind it
(`pipeline_depth` 2), its own prefill program, and then joins the next
one: about two blocks. `EngineConfig.decode_steps_per_dispatch` is a
number of STEPS, and a step is 11 ms in one model and 29 ms in another,
so the same eight steps cost an arrival 94 ms here and 230 ms there.
The block is therefore held to a time budget WHILE somebody can be
waiting for it: what an ARRIVAL can afford (BLOCK_BUDGET_MS), not what
eight steps of some model happen to cost. Whenever nobody can be
waiting a block is the configured length.

Nothing here touches the device, a clock or the engine: tests call the
function with plain numbers.
"""

from __future__ import annotations

import statistics
from collections import deque
from typing import Collection, Deque, Optional

# What one block may cost an arrival, in milliseconds of device time.
# An arrival waits about two of them (the rest of the running block and
# the one queued behind it), so 60 holds the queue's share of a freed
# slot's empty time near 100-120 ms at ANY step time: a slot that stands
# empty longer costs more tokens than short blocks do (a two-step
# block's step reads within about 1 % of an eight-step one's, on one
# chip and on four: PERF.md section 6, PR 51). With the warm set
# {1, 2, ceiling} every step over 60 / ceiling ms (7.5 at eight steps)
# gets the short block; a faster model's eight steps fit and stay, which
# is why this is a budget and not "always SHORT_K".
BLOCK_BUDGET_MS = 60.0
# The block of the low-occupancy regime, which warm-up compiles beside
# K = 1 and the ceiling: the shortest block a time budget asks for.
SHORT_K = 2
# Landed blocks a step time is the median of: a stalled program or a
# late stamp is one sample of these, never the estimate.
STEP_SAMPLES = 5


def round_to_warm(bound: int, warm: Collection[int]) -> int:
    """Largest dispatchable K <= bound: a power of two and, when a
    warm-up ran (`warm` non-empty), one of the precompiled variants.
    K = 1 is in every warm set, so a warm K exists under any bound."""
    k = max(1, bound)
    while k & (k - 1):
        k &= k - 1
    if warm and k not in warm:
        k = max(w for w in warm if w <= k)
    return k


def choose_k(configured: int, warm: Collection[int], live: int, slots: int,
             arrival_waiting: bool, long_prefill_cap: int,
             step_ms: Optional[float], budget_ms: float) -> int:
    """Steps of the next decode block, before the page and token bounds
    of the slots that ride in it.

    configured        `decode_steps_per_dispatch`, the ceiling
    warm              the precompiled K's (empty: no warm-up ran, any
                      power of two dispatches)
    live, slots       decodable slots and the batch's rows
    arrival_waiting   a slot is empty, a request is queued, or a slot's
                      prefill is enqueued and its first block is not
    long_prefill_cap  `prefill_decode_k_cap` while a chunked prefill is
                      in progress, else 0
    step_ms           device time of one decode step as the last landed
                      blocks read, None until one has landed
    budget_ms         what one block may cost an arrival: the engine
                      passes BLOCK_BUDGET_MS
    """
    k = max(1, configured)
    if live * 4 <= slots:
        # Low occupancy (arrival-heavy): a new arrival's prefill is
        # never stuck behind K weight reads of mostly-empty decode work.
        k = min(k, SHORT_K)
    if long_prefill_cap > 0:
        # Chunked-prefill priority lane: prefill chunks interleave with
        # decode at a fine grain.
        k = min(k, long_prefill_cap)
    if arrival_waiting and step_ms and k * step_ms > budget_ms:
        # Somebody waits out this block: the longest one the budget
        # holds, and no shorter than the short block.
        k = min(k, max(SHORT_K, int(budget_ms // step_ms)))
    return round_to_warm(k, warm)


class StepTime:
    """Device time of one decode step, from the blocks that landed: the
    median of the last few blocks' (start -> ready) / K."""

    def __init__(self):
        self._recent: Deque[float] = deque(maxlen=STEP_SAMPLES)

    def note(self, ran_ms: float, k: int) -> None:
        if ran_ms > 0.0 and k > 0:
            self._recent.append(ran_ms / k)

    @property
    def ms(self) -> Optional[float]:
        return statistics.median(self._recent) if self._recent else None
