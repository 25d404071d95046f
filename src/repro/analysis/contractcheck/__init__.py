"""contractcheck — AST-based enforcement of the engine's prose contracts.

Six composable checkers walk ``src/``, ``tests/`` and ``benchmarks/`` and
turn the invariants of docs/DESIGN.md §3/§8/§9 and ROADMAP's "Constraints &
contracts" into errors (docs/DESIGN.md §11 maps each id to its clause):

=====================  ====================================================
checker id             contract
=====================  ====================================================
``shim-discipline``    JAX mesh APIs: raw ``jax.sharding.Mesh``/
                       ``AxisType``/``use_mesh``, ``jax.set_mesh``,
                       ``shard_map`` and ``Mesh(...)`` construction are
                       only legal in ``launch/mesh.py``.
``lock-discipline``    one-lock concurrency (§8): guarded-state mutations
                       only under ``self._cond`` or in ``# contract:
                       holds-lock`` helpers; EngineStats fields are only
                       written by ``_bump``/``stat_bump``/``reset_stats``.
``blocking-under-lock``no device waits / sleeps / condvar waits / host
                       conversions of attribute state while the lock is
                       held, except the ``# contract: syncer-handoff``
                       whitelisted handoff path.
``device-residency``   ``# contract: device-resident`` functions never
                       materialize traced values on the host (§6).
``shard-purity``       shard-parameterized helpers thread the explicit
                       shard index into every per-shard container (§9).
``store-encapsulation``block-store LRU internals (``._store``,
                       ``._arrays``) are only touched inside
                       ``core/blockstore.py`` and its white-box test;
                       everyone else uses the engine's public
                       ``clear_cache()`` / ``cache_nbytes()``.
=====================  ====================================================

Library use::

    from repro.analysis.contractcheck import run_checks
    violations = run_checks(["src", "tests", "benchmarks"])

Everything is stdlib-only (``ast`` + ``tokenize``): the CI static-analysis
job runs without jax installed. The analysis is lexical by design — see
``locks.py`` — which is exactly what makes the annotations reviewable.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

from .base import (Checker, Config, ModuleContext, Violation,
                   iter_python_files)
from .locks import BlockingUnderLock, LockDiscipline
from .residency import DeviceResidency, StoreEncapsulation
from .shards import ShardPurity
from .shim import ShimDiscipline

__all__ = [
    "CHECKERS", "Checker", "Config", "ModuleContext", "Violation",
    "run_checks",
]

#: default checker set, in documentation order
CHECKERS = (ShimDiscipline(), LockDiscipline(), BlockingUnderLock(),
            DeviceResidency(), ShardPurity(), StoreEncapsulation())


def run_checks(paths: Iterable, config: Optional[Config] = None,
               checkers: Optional[Sequence[Checker]] = None
               ) -> List[Violation]:
    """Run every checker over the ``.py`` files under ``paths`` (files or
    directories) and return the violations sorted by (path, line, checker),
    de-duplicated by fingerprint. A file that fails to parse yields a
    single ``parse-error`` violation instead of aborting the run."""
    cfg = config or Config()
    active = CHECKERS if checkers is None else tuple(checkers)
    out: List[Violation] = []
    for f in iter_python_files(paths, cfg):
        try:
            ctx = ModuleContext.from_file(f)
        except (SyntaxError, UnicodeDecodeError) as e:
            out.append(Violation(
                path=f.as_posix(), line=getattr(e, "lineno", 1) or 1,
                checker="parse-error", message=f"file does not parse: {e}"))
            continue
        for checker in active:
            out.extend(checker.check(ctx, cfg))
    seen = set()
    uniq = []
    for v in sorted(out, key=lambda v: (v.path, v.line, v.checker)):
        if v.fingerprint not in seen:
            seen.add(v.fingerprint)
            uniq.append(v)
    return uniq
