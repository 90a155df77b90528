"""The tolerance ledger: per-family error budgets, encoded ONCE.

Every numeric claim the fuzzer enforces lives here.  The bf16/fp32
kernel families are held to the reference's frozen ±0.02 elementwise
contract (`attention.c:143` — `core.testcase.VERIFY_THRESHOLD`); the
quantized caches are held to their MEASURED budgets
(tests/test_quant.py): int8 sits comfortably inside the contract, int4 is
an opt-in bytes/quality trade whose budget is ~4x the contract (and ~2x
again under a sliding window, where fewer softmax terms average less of
the nibble noise out).

PARITY.md's "Tolerance ledger" table is a human-readable mirror of
:data:`FAMILY_BUDGETS`; ``scripts/check_tolerances.py`` lints the two
against each other (the `check_shipped_table.py` discipline), so a
budget can never drift in only one place.
"""

from __future__ import annotations

from attention_tpu.core.testcase import VERIFY_THRESHOLD

#: the reference harness contract (attention.c:143)
CONTRACT_TOL = VERIFY_THRESHOLD  # 0.02

#: max-abs-error budget per fuzz family (unit-normal inputs, fp64
#: oracle).  Keys are fuzz family names plus the ``int4_short``
#: variant: int4's nibble noise averages out over the softmax band, so
#: the budget is conditioned on how many KV rows a query attends —
#: a sliding window or a short ragged prefix (< INT4_FULL_BAND rows)
#: gets the wider short-band budget.  Both int4 values are the chaos
#: fuzzer's own 40-seed worst-case measurement at d=64 (full band
#: ~0.20, 8-row band ~0.29, plus margin) — WIDER than test_quant's
#: few-seed typical figure of ~4-8e-2, which sits near the center of
#: the distribution, not its tail.
FAMILY_BUDGETS: dict[str, float] = {
    "flash": CONTRACT_TOL,   # fused Pallas forward (fp32/bf16)
    "decode": CONTRACT_TOL,  # dense-cache flash decode
    "paged": CONTRACT_TOL,   # page-table decode
    "ragged": CONTRACT_TOL,  # packed mixed decode/prefill launch
    "int8": CONTRACT_TOL,    # int8 KV cache: measured ~2e-3, held to
                             # the contract (it is contract-grade)
    "int4": 0.25,            # full-band worst case (~0.20 measured)
    "int4_short": 0.35,      # windowed / short-band (~0.29 measured)
    "flashd": CONTRACT_TOL,  # FLASH-D rescaling variant: same fp32
                             # softmax math reassociated (the division
                             # moves into the tile update), measured
                             # ~5e-7 fp32 / ~8e-3 bf16 vs online —
                             # held to the contract across every
                             # max_mode-threading family
    "amla": CONTRACT_TOL,    # AMLA rescaling variant: pow2 rescales
                             # are BIT-EXACT (exponent-field adds);
                             # only the quantized max shifts which
                             # exp2 rounding each term sees — measured
                             # at online's own error scale, held to
                             # the contract likewise
}

#: minimum attended-band width (KV rows) for int4's full-band budget
INT4_FULL_BAND = 64


def tolerance_for(family: str, *, window: int | None = None,
                  min_band: int | None = None,
                  max_mode: str | None = None) -> float:
    """The ledger's budget for one sampled config.

    ``min_band`` is the narrowest softmax band any query in the case
    attends (min over sequences of ``min(length, window)``); int4's
    budget widens below :data:`INT4_FULL_BAND` rows.  ``max_mode``
    names the rescaling-math variant the case lowers: the flashd/amla
    variants carry their OWN ledger rows (one budget per variant,
    whichever family threads it — the variant changes the in-kernel
    recurrence, not the family's masking), while online/bound keep the
    family's row (bound is bit-identical softmax by max-invariance).
    """
    if max_mode in ("flashd", "amla"):
        return FAMILY_BUDGETS[max_mode]
    if family == "int4" and (
        window is not None
        or (min_band is not None and min_band < INT4_FULL_BAND)
    ):
        return FAMILY_BUDGETS["int4_short"]
    try:
        return FAMILY_BUDGETS[family]
    except KeyError:
        raise ValueError(
            f"no tolerance budget for family {family!r}; known: "
            f"{sorted(FAMILY_BUDGETS)}"
        ) from None
