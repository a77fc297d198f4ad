"""repro.analysis — the AST-based invariant lint plane ("replint").

The reproduction's bit-identity discipline (golden replays, fuzz
differentials, the accounting-invariant suite) catches mirror-desync,
metrics-leak and nondeterminism bugs at *runtime*, after a differential has
to run.  This package certifies the same bug classes *statically*: a small
rule engine walks every source module's AST and reports repo-specific
invariant violations with file:line precision, before any replay runs.

Rule families (DESIGN.md §15 is the catalog):

* ``mirror-sync`` / ``dirty-notify`` — writes to skyline / probe-plane /
  ``_LPMirror`` buffers outside the calendar mutation API, and calendar
  mutation paths missing the dirty-mark notification (the stale-mirror
  class PR 4/5 could only catch by fuzzing).
* ``terminal-state`` — terminal ``TaskState`` assignments outside the
  designated settle helpers audited by tests/test_accounting_invariants.py
  (the PR 6 metrics-leak class).
* ``determinism-wallclock`` / ``determinism-rng`` / ``determinism-set-iter``
  — wall-clock reads, unseeded RNG, and unordered set iteration inside the
  ``core/`` + ``sim/`` decision paths.
* ``pallas-index`` / ``jax-free-boundary`` — bare-int ``pl.load`` /
  ``pl.store`` / ``pl.swap`` indices (rejected in interpret mode by older
  JAX; jax 0.9.0 has no such functions) and module-level jax imports in
  the streaming-path modules PR 7 deliberately kept jax-free.

Suppression is explicit and line-scoped: ``# replint: disable=<rule>`` on
the flagged line, or an entry in the committed baseline file
(``replint_baseline.json``) carrying a one-line justification.  Run as
``python -m repro.analysis [--gate]``; the CI gate blocks on any
unbaselined finding and on stale baseline entries.
"""
from .engine import (
    Finding,
    Module,
    Report,
    Rule,
    default_rules,
    finding_key,
    load_baseline,
    run_analysis,
)
from .rules.determinism import SetIterRule, UnseededRngRule, WallClockRule
from .rules.kernel_rules import JaxImportRule, PallasIndexRule
from .rules.mirror_sync import DirtyNotifyRule, MirrorWriteRule
from .rules.terminal_state import SETTLE_HELPERS, TerminalStateRule

__all__ = [
    "Finding",
    "Module",
    "Report",
    "Rule",
    "default_rules",
    "finding_key",
    "load_baseline",
    "run_analysis",
    "MirrorWriteRule",
    "DirtyNotifyRule",
    "TerminalStateRule",
    "SETTLE_HELPERS",
    "WallClockRule",
    "UnseededRngRule",
    "SetIterRule",
    "PallasIndexRule",
    "JaxImportRule",
]
