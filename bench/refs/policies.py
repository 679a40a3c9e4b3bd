"""Plain host references of the six sweep policies (AWRP, LRU, FIFO, LFU,
ARC, CAR): one cache, one access at a time, per-access hits out.

They follow the definitions the program's host oracles state
(``repro.core.policies``) and import nothing of the program:

* AWRP (arXiv:1107.4851 eq. (1)): clock N counts accesses; a hit sets
  F += 1, R = N; a miss fills the first empty slot, else evicts the first
  slot of least W = F / max(N - R, 1); the new block gets F = 1, R = N.
* LRU, FIFO; LFU with ties to the least recent.
* ARC (Megiddo & Modha 2003) and CAR (Bansal & Modha 2004), with the
  adaptation parameter ``p`` kept in float32.

``dtype`` is the precision of every quotient (AWRP's weights, ARC/CAR's
``p`` step).  The configuration states float32; ``bfloat16`` is the
control that must fail the comparison.
"""

from __future__ import annotations

from collections import OrderedDict, deque

import ml_dtypes
import numpy as np

#: the policies whose state is flat slot planes (the victim kernel's rows)
FLAT = ("awrp", "lru", "fifo", "lfu")

PRECISIONS = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


def awrp(trace, cap: int, dt=np.float32) -> np.ndarray:
    blocks = np.full(cap, -1, np.int64)
    F = np.zeros(cap, np.int64)
    R = np.zeros(cap, np.int64)
    where: dict = {}
    out = np.zeros(len(trace), bool)
    clock = used = 0
    for t, b in enumerate(trace.tolist()):
        clock += 1
        s = where.get(b)
        if s is not None:
            F[s] += 1
            R[s] = clock
            out[t] = True
            continue
        if used < cap:
            s = used
            used += 1
        else:
            w = (F.astype(dt) / np.maximum(clock - R, 1).astype(dt)).astype(dt)
            s = int(np.argmin(w))
            del where[int(blocks[s])]
        blocks[s], F[s], R[s] = b, 1, clock
        where[b] = s
    return out


def lru(trace, cap: int, dt=np.float32) -> np.ndarray:
    od: OrderedDict = OrderedDict()
    out = np.zeros(len(trace), bool)
    for t, b in enumerate(trace.tolist()):
        if b in od:
            od.move_to_end(b)
            out[t] = True
            continue
        if len(od) >= cap:
            od.popitem(last=False)
        od[b] = None
    return out


def fifo(trace, cap: int, dt=np.float32) -> np.ndarray:
    q: deque = deque()
    s: set = set()
    out = np.zeros(len(trace), bool)
    for t, b in enumerate(trace.tolist()):
        if b in s:
            out[t] = True
            continue
        if len(q) >= cap:
            s.discard(q.popleft())
        q.append(b)
        s.add(b)
    return out


def lfu(trace, cap: int, dt=np.float32) -> np.ndarray:
    """Evicts the least frequent block, the least recent among equals."""
    blocks = np.full(cap, -1, np.int64)
    key = np.zeros(cap, np.int64)  # freq << 32 | last access
    where: dict = {}
    out = np.zeros(len(trace), bool)
    used = 0
    for t, b in enumerate(trace.tolist()):
        clock = t + 1
        s = where.get(b)
        if s is not None:
            key[s] = ((key[s] >> 32) + 1) << 32 | clock
            out[t] = True
            continue
        if used < cap:
            s = used
            used += 1
        else:
            s = int(np.argmin(key))
            del where[int(blocks[s])]
        blocks[s], key[s] = b, 1 << 32 | clock
        where[b] = s
    return out


def arc(trace, cap: int, dt=np.float32) -> np.ndarray:
    T1, T2, B1, B2 = OrderedDict(), OrderedDict(), OrderedDict(), OrderedDict()
    p = dt(0.0)
    out = np.zeros(len(trace), bool)

    def replace(b):
        if T1 and ((b in B2 and len(T1) == int(p)) or len(T1) > int(p)):
            B1[T1.popitem(last=False)[0]] = None
        else:
            B2[T2.popitem(last=False)[0]] = None

    for t, b in enumerate(trace.tolist()):
        if b in T1:
            del T1[b]
            T2[b] = None
            out[t] = True
        elif b in T2:
            T2.move_to_end(b)
            out[t] = True
        elif b in B1:
            delta = max(dt(dt(len(B2)) / dt(max(len(B1), 1))), dt(1.0))
            p = min(dt(cap), dt(p + delta))
            replace(b)
            del B1[b]
            T2[b] = None
        elif b in B2:
            delta = max(dt(dt(len(B1)) / dt(max(len(B2), 1))), dt(1.0))
            p = max(dt(0.0), dt(p - delta))
            replace(b)
            del B2[b]
            T2[b] = None
        else:
            if len(T1) + len(B1) == cap:
                if len(T1) < cap:
                    B1.popitem(last=False)
                    replace(b)
                else:
                    T1.popitem(last=False)
            else:
                total = len(T1) + len(T2) + len(B1) + len(B2)
                if total >= cap:
                    if total == 2 * cap:
                        B2.popitem(last=False)
                    replace(b)
            T1[b] = None
    return out


def car(trace, cap: int, dt=np.float32) -> np.ndarray:
    # clocks: deque in hand order (head = hand) + reference bits
    T1, T2 = deque(), deque()
    ref1, ref2 = {}, {}
    B1, B2 = OrderedDict(), OrderedDict()
    p = dt(0.0)
    out = np.zeros(len(trace), bool)

    def replace():
        while True:
            if len(T1) >= max(1, int(p)):
                b = T1.popleft()
                if not ref1.pop(b):
                    B1[b] = None
                    return
                T2.append(b)
                ref2[b] = False
            else:
                b = T2[0]
                if not ref2[b]:
                    T2.popleft()
                    del ref2[b]
                    B2[b] = None
                    return
                ref2[b] = False
                T2.rotate(-1)

    for t, b in enumerate(trace.tolist()):
        if b in ref1:
            ref1[b] = True
            out[t] = True
            continue
        if b in ref2:
            ref2[b] = True
            out[t] = True
            continue
        in_b1, in_b2 = b in B1, b in B2
        if len(T1) + len(T2) == cap:
            replace()
            if not in_b1 and not in_b2:
                if len(T1) + len(B1) == cap + 1:
                    B1.popitem(last=False)
                elif len(T1) + len(T2) + len(B1) + len(B2) >= 2 * cap:
                    B2.popitem(last=False)
        if not in_b1 and not in_b2:
            T1.append(b)
            ref1[b] = False
        elif in_b1:
            delta = max(dt(1.0), dt(dt(len(B2)) / dt(max(len(B1), 1))))
            p = min(dt(cap), dt(p + delta))
            del B1[b]
            T2.append(b)
            ref2[b] = False
        else:
            delta = max(dt(1.0), dt(dt(len(B1)) / dt(max(len(B2), 1))))
            p = max(dt(0.0), dt(p - delta))
            del B2[b]
            T2.append(b)
            ref2[b] = False
    return out


POLICIES = {"awrp": awrp, "lru": lru, "fifo": fifo, "lfu": lfu, "arc": arc,
            "car": car}


def hits(policy: str, trace, cap: int, precision: str = "float32"):
    """Per-access hits of one cache of ``cap`` blocks over ``trace``."""
    return POLICIES[policy](np.asarray(trace), int(cap),
                            PRECISIONS[precision])
