"""grid-carry-init: VMEM scratch proven written-before-read across steps.

Pallas VMEM scratch persists across grid steps but is **uninitialized**
at grid step 0 — the classic kernel bug is an accumulator ``+=`` that
runs before anything stored to the scratch on the current block.  The
streaming-accumulation kernel avoids it with the ``first`` predicate:
``@pl.when(first)`` zero/initialize-stores, ``@pl.when(not first)``
accumulates.  The correctness of that idiom hinges on one easily-lost
detail: the block-boundary test MUST be wrapped with ``t == 0``
(``jnp.logical_or(t == 0, blk != tile_block_ref[jnp.maximum(t - 1, 0)])``),
because at ``t == 0`` the ``t - 1`` look-behind is clamped to tile 0 (or,
unclamped, wraps to the LAST tile) and the boundary test alone may
evaluate false — leaving block 0's scratch uninitialized.

This pass proves the write-before-read property statically from the
symbolic traffic interpreter's predicated access sites (textual order is
execution order — ``pl.when`` bodies execute at their definition point).
A scratch READ at a site is safe iff

  (a) a textually-earlier STORE to the same ref is predicated
      ``every-step`` or ``block-first`` (scratch persists across steps,
      so the block's first step initialized it before any later step's
      read), or
  (b) the read itself is predicated ``block-interior`` (¬first) and the
      kernel contains an every-step/block-first store anywhere — by
      induction, the block's first step ran the initializing store.

A store predicated on an UNWRAPPED boundary test (``block-first`` minus
the ``t == 0`` term) does not qualify as the initializer — it misses
grid step 0 — and is itself a finding.
"""

from __future__ import annotations

from repro.analysis.core import AnalysisContext, Checker, register
from repro.analysis.traffic import AccessSite, Pred, find_traffic_censuses

#: Store predicates that prove the scratch initialized for the block.
INITIALIZING_PREDS = (Pred.EVERY, Pred.FIRST)


@register
class GridCarryInit(Checker):
    check_id = "grid-carry-init"
    description = (
        "Pallas VMEM scratch is written (every-step or wrap-guarded "
        "block-first) before any grid-carried read; unwrapped boundary "
        "predicates that miss grid step 0 are flagged"
    )

    def run(self, ctx: AnalysisContext) -> None:
        proven: list[dict] = []
        files = ctx.scannable("src/", "tests/")
        censuses, _skipped = find_traffic_censuses(files)
        for census in censuses:
            if census.kind != "pallas" or not census.scratch_refs:
                continue
            sf = ctx.file(census.file)
            if sf is None:
                continue
            scratch = set(census.scratch_refs)
            sites = [s for s in census.sites if s.ref in scratch]
            reads_proven = 0
            initialized: set[str] = set()
            has_init_store = {
                ref: any(
                    s.ref == ref and s.op == "store"
                    and s.pred in INITIALIZING_PREDS
                    for s in sites
                )
                for ref in scratch
            }
            for s in sites:
                if s.op == "store":
                    if s.pred in INITIALIZING_PREDS:
                        initialized.add(s.ref)
                    elif s.pred == Pred.FIRST_NO_WRAP:
                        self.emit(
                            sf, s.line,
                            f"{s.fn}: store to scratch {s.ref!r} is guarded "
                            "by a block-boundary test without the t==0 wrap "
                            "guard — at grid step 0 the t-1 look-behind "
                            "cannot see a boundary and block 0's scratch "
                            "stays uninitialized",
                        )
                    continue
                # load or rmw — a read of grid-carried scratch
                if s.ref in initialized:
                    reads_proven += 1
                    continue
                if s.pred == Pred.NOT_FIRST and has_init_store[s.ref]:
                    reads_proven += 1
                    continue
                self.emit(
                    sf, s.line,
                    f"{s.fn}: read of VMEM scratch {s.ref!r} "
                    f"(predicate: {s.pred}) is not preceded by an "
                    "every-step or wrap-guarded block-first store — at "
                    "grid step 0 the scratch is uninitialized garbage",
                )
            proven.append(
                {
                    "program": census.program,
                    "file": census.file,
                    "kernel": census.kernel_fn,
                    "scratch_refs": sorted(scratch),
                    "reads_proven": reads_proven,
                }
            )
        self.facts["programs"] = proven
