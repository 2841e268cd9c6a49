"""abclint — repo-specific static analysis for the ABC serving stack.

Four AST passes enforce the invariants PRs 1–5 earned dynamically
(compile-once, device-resident, bit-deterministic, kernel-contract) across
``src/repro``, ``benchmarks`` and ``tools``:

  retrace          ABC101-103  jit/pallas_call program-cache discipline
  host_sync        ABC201-204  metered-_fetch/Transport boundary discipline
  determinism      ABC301-303  no hash()/set-order/wall-clock nondeterminism
  kernel_contract  ABC401-405  ops/kernel/ref trio, interpret=, typed errors

Run: ``python -m tools.abclint`` (see ``--help``); policy: DESIGN.md §9.
"""
from tools.abclint.engine import (  # noqa: F401
    Finding,
    Pass,
    RunResult,
    load_baseline,
    run,
    run_passes,
    write_baseline,
)
from tools.abclint.passes import ALL_PASSES, ALL_RULES  # noqa: F401
