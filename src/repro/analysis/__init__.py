"""Dynamic and static analysis tooling for the repro codebase.

* :mod:`repro.analysis.kvsan` — page-lifetime sanitizer over the serving
  engines' :class:`~repro.serving.kvpool.PagePool` (``REPRO_KVSAN=1``).
* :mod:`repro.analysis.invariants` — control-plane invariant checker run
  on every router tick when the sanitizer is enabled.
* :mod:`repro.analysis.lint` — repo-specific AST lint
  (``python -m repro.analysis.lint``).
* :mod:`repro.analysis.fuzz` — randomized replay fuzzer that drives the
  router under the sanitizer (``python -m repro.analysis.fuzz``);
  ``--compile-audit`` also arms the compile tracker per round.
* :mod:`repro.analysis.compile_tracker` — recompile-budget interposer
  over the hot-path jit caches (``REPRO_JITAUDIT=1``).
* :mod:`repro.analysis.jitaudit` — static compile-plane auditor:
  donation verification, retrace-hazard probes, and static rooflines
  over the traced jaxprs/HLO (``python -m repro.analysis.jitaudit``).
* :mod:`repro.analysis.spans` — host spans and self-time counters of
  the serving path (always on; read from ``RouterMetrics``).

This ``__init__`` stays import-light on purpose: ``kvpool`` and
``radix_tree`` import :mod:`repro.analysis.kvsan` at module load, so
anything heavier here would tax every engine import.
"""
