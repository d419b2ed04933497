"""``repro.analysis`` — the JAX/Pallas contract linter (DESIGN.md §14).

Two layers mechanically enforce the correctness invariants this repo
has shipped-and-fixed one regression at a time:

* **Layer 1 (AST)** — ``ast_rules``: pluggable source rules for the
  PR 1 literal-ref-index class, the PR 3 weak-carry recompile class,
  host syncs / Python branches inside traced code, and PRNG key reuse.
* **Layer 2 (jaxpr)** — ``contracts`` + ``registry``: abstract traces
  of the registered entry points (core run/scheduled, the replica and
  consensus steps, the sharded fleet comm plans, the fused broadcast
  select) checked for host callbacks, weak scan carries,
  branch-divergent collectives, and unpinned FMA seams (the PR 7
  bit-parity contract).

CLI: ``python -m repro.analysis --strict`` (the CI gate). Inline
suppression: ``# repro: allow[rule-id] -- justification``.
"""
from .ast_rules import RULES, run_rules
from .contracts import CONTRACT_IDS, check_entry_point, run_contracts
from .findings import Finding
from .registry import EntryPoint, iter_entry_points

__all__ = [
    "CONTRACT_IDS", "EntryPoint", "Finding", "RULES",
    "check_entry_point", "iter_entry_points", "run_contracts", "run_rules",
]
