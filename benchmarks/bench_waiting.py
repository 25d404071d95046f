"""Paper Tables 5/6/7 + Fig. 10: consumer waiting-time breakdown
(request push / in queue / data preparation / kernel dispatch / sync wait /
integration) per algorithm and consumer width, from the engine's phase
accounting.

Overlap A/B: every (algorithm, width) cell runs twice — ``async=on`` (the
engine's in-flight futures producer) and ``async=off`` (block on every
launch) — after an untimed warmup so neither arm pays jit compilation. The
``sync_s`` column is the paper's "waiting" metric: time the consumer
actually stalled on a block that was still computing. Each pair emits an
``overlap`` row: ``kernel_total_s`` is the total kernel time the blocking
arm measured (dispatch + unavoidable wait) and ``overlap_ok`` records
whether the async consumer's ``sync_s`` stayed strictly below it, i.e.
kernel execution was hidden behind consumer work (the paper's Fig. 2(b)
claim); ``hidden_s`` is how much was hidden. A final verification row
checks that async-produced relation blocks are bit-identical to the
blocking path's.
"""

from __future__ import annotations

from typing import List

import numpy as np

from repro.algorithms.critical_points import critical_points
from repro.algorithms.discrete_gradient import discrete_gradient
from repro.algorithms.morse_smale import morse_smale

from . import common
from .bench_algorithms import CP_RELS, DG_RELS, MS_RELS


def _fmt(st, total):
    wait = st.t_enqueue + st.t_queue + st.t_prepare + st.t_dispatch \
        + st.t_sync + st.t_integrate
    return (f"total_s={total:.3f};wait_s={wait:.3f};"
            f"push_s={st.t_enqueue:.4f};queue_s={st.t_queue:.4f};"
            f"prep_s={st.t_prepare:.4f};dispatch_s={st.t_dispatch:.4f};"
            f"sync_s={st.t_sync:.4f};integrate_s={st.t_integrate:.4f};"
            f"requests={st.requests};hits={st.cache_hits};"
            f"inflight_hits={st.inflight_hits};misses={st.cache_misses}")


def _verify_async_identical(pre, rels) -> bool:
    """Async-produced blocks must be bit-identical to the blocking path."""
    a = common.make_ds("gale", pre, rels, async_dispatch=True)
    b = common.make_ds("gale", pre, rels, async_dispatch=False)
    ns = pre.smesh.n_segments
    for R in a.relations:
        a.prefetch(R, range(min(ns, 8)))
    for R in a.relations:
        for s in range(0, ns, max(1, ns // 16)):
            Ma, La = a.get(R, s)
            Mb, Lb = b.get(R, s)
            if not (np.array_equal(Ma, Mb) and np.array_equal(La, Lb)):
                return False
    return True


def run(quick: bool = True) -> List[str]:
    dataset = "fish" if quick else "stent"
    rows = []
    algos = (
        ("critical_points", CP_RELS,
         lambda ds, pre, rank, w: critical_points(ds, pre, rank,
                                                  batch_segments=w)),
        ("discrete_gradient", DG_RELS,
         lambda ds, pre, rank, w: discrete_gradient(ds, pre, rank,
                                                    batch_segments=w)),
        ("morse_smale", MS_RELS,
         lambda ds, pre, rank, w: morse_smale(
             ds, pre, discrete_gradient(ds, pre, rank, batch_segments=w))),
    )
    widths = (1, 16) if quick else (1, 8, 16, 32)
    for algo, rels, fn in algos:
        sm, pre, rank, _ = common.prepare(dataset, rels)
        for w in widths:
            stats = {}
            for use_async in (True, False):
                # untimed warmup so neither A/B arm pays jit compilation
                common.timed(fn, common.make_ds(
                    "gale", pre, rels, async_dispatch=use_async),
                    pre, rank, w)
                ds = common.make_ds("gale", pre, rels,
                                    async_dispatch=use_async)
                t, _ = common.timed(fn, ds, pre, rank, w)
                tag = "async" if use_async else "blocking"
                stats[tag] = ds.stats
                rows.append(common.row(
                    f"waiting/{algo}/{dataset}/consumers{w}/{tag}", t,
                    _fmt(ds.stats, t)))
            # Overlap verdict for the pair: total kernel time is what the
            # blocking arm measured (dispatch + the wait it cannot avoid);
            # overlap_ok iff the async consumer waited strictly less than
            # that, i.e. kernel execution was (partially) hidden behind
            # consumer work — the paper's Fig. 2(b) claim.
            kern = stats["blocking"].t_dispatch + stats["blocking"].t_sync
            hidden = kern - stats["async"].t_sync
            rows.append(common.row(
                f"waiting/{algo}/{dataset}/consumers{w}/overlap", hidden,
                f"kernel_total_s={kern:.4f};"
                f"async_sync_s={stats['async'].t_sync:.4f};"
                f"hidden_s={hidden:.4f};"
                f"overlap_ok={stats['async'].t_sync < kern}"))
        rows.append(common.row(
            f"waiting/{algo}/{dataset}/async_bit_identical", 0.0,
            f"identical={_verify_async_identical(pre, rels)}"))
    return rows
