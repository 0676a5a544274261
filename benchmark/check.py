"""The comparison that decides ``correct``: the system against the plain
reference, with each tolerance and the reason for it."""

import numpy as np

# Serving logits are float32 outputs of bf16 matmuls (8 bits of mantissa); the
# reference computes the same bf16 weights in float32 at "highest" precision.
# Every layer adds a few roundings of ~2^-9 relative to the residual stream,
# independent of the last layer's, so the error of the logits grows as a random
# walk: with the square root of the depth. Measured on the chip against this
# reference (PR 22): 3 layers of Mixtral agree to 2^-7.4..2^-7.9 of the largest
# logit in every row, 12 layers of Mistral (contexts of 860-2730 tokens, bf16
# attention probabilities) to 2^-6.0..2^-6.7. Allow 2^-7 x sqrt(layers): 2^-6.2
# at 3 layers (chip_smoke's 2^-6, whose argument this is), 2^-5.2 at 12, about
# twice what was seen. A wrong mask, a wrong block or a dropped expert
# assignment is off by the size of the logits themselves (the MoE drop case in
# tests/benchmark/test_references.py misses by 2^-2), and weights or products in
# a lower precision (fp8, int8: 2^-4 per product where bf16 has 2^-9) by 2^4 or
# more times what bf16 shows at the same depth.
LOGIT_REL_TOL_ONE_LAYER = 2.0**-7


def logit_rel_tol(n_layers):
    return LOGIT_REL_TOL_ONE_LAYER * float(np.sqrt(n_layers))


# A sparse model's routing is a discontinuity: where the last expert a token
# chooses is ahead of the first one it leaves out by less than the rounding
# noise of the router's input, the system and the reference may choose
# differently, both rightly, and that token's output then differs by a share of
# one expert's contribution, not by a rounding. The router logits here are O(1)
# and the system's hidden states carry ~2^-7 relative error by the time they
# reach a router, so a gap under 2^-5 logit units is a toss-up. A row whose
# position has such a gap in any layer is held to four times the tolerance;
# every other row (three in four at 3 layers) to the tight one, which is what
# catches a lower precision. The loose one still catches a wrong mask or block.
# First chip runs, PR 22: of 8 prompts x 9 rows, one row read 2^-6.3 and one
# 2^-5.7 where the rest read 2^-7.4..2^-7.7.
ROUTING_TOSS_UP_GAP = 2.0**-5
TOSS_UP_TOL_FACTOR = 4.0


# A configuration may state the limits of its own rows, each set between two
# readings on the chip (PERF.md section 2 keeps them): a ``check`` group of
# ``tight_row_log2`` and ``loose_row_log2`` (a row's largest error, as log2 of
# the largest logit, off and at a toss-up) and ``median_row_log2`` (the MEDIAN
# row's error over all the rows of a run's check). The worst row is a widest
# gap and swings from seed to seed (one row in some hundreds reads twice the
# next worst), so the lower precision stands a bare 2.8 x over the largest honest
# row; it moves EVERY row, and stands 5 x over the honest median, which twelve
# seeds read within 0.22 bits. A configuration that states nothing is held as it
# always was: 2^-7 x sqrt(layers), 4 x that at a toss-up, no median.
def row_limits(sizes):
    """``{"tight", "loose", "median"}``: the shares of the largest logit that a
    served configuration's rows are held to (``median`` None where none is)."""
    stated = sizes.get("check") or {}
    tight = 2.0**stated["tight_row_log2"] if "tight_row_log2" in stated \
        else logit_rel_tol(sizes["num_hidden_layers"])
    loose = 2.0**stated["loose_row_log2"] if "loose_row_log2" in stated \
        else tight * TOSS_UP_TOL_FACTOR
    median = 2.0**stated["median_row_log2"] if "median_row_log2" in stated else None
    return {"tight": float(tight), "loose": float(loose), "median": median}


def rows_compared(row_errors, limits):
    """``row_errors``: ``(error / largest logit, held to the loose limit)`` for
    every row of a run's check. Returns ``(ok, compared)``: each number compared
    beside its limit, ``{name: [value, limit]}``, and whether all are inside."""
    err = np.asarray([e for e, _ in row_errors], np.float64)
    loose = np.asarray([k for _, k in row_errors], bool)
    compared = {}
    if (~loose).any():
        compared["worst_tight_row"] = [float(err[~loose].max()), limits["tight"]]
    if loose.any():
        compared["worst_loose_row"] = [float(err[loose].max()), limits["loose"]]
    if limits["median"] is not None:
        compared["median_row"] = [float(np.median(err)), limits["median"]]
    return all(v <= limit for v, limit in compared.values()), compared

# Training: the engine's first loss is a bf16 forward; the reference is float32.
# The loss is a mean over 4096 tokens of a log-softmax near ln(vocab). Rounding of
# the logits by e (zero mean) raises log-sum-exp by about var(e)/2: bf16 logits
# carry e ~ 2^-7 of a scale of ~4, so the loss sits ~5e-5 (relative) off the
# reference's, and the chip read just that in every run (PR 22: 10.8788 against
# 10.8792, 10.8828 / 10.8829, 10.8791 / 10.8794: 1e-5..4e-5). Allow 5e-4, ten
# times what was seen. Products in fp8 (2^-4 a product) put e near 0.5 and the
# loss ~1e-2 off: caught. WHAT THIS DOES NOT CATCH: a fresh model's loss is
# ~ln(vocab) whatever the mask, so a wrong window or a wrong causal mask passes
# here. The training forward is held to the reference's per-token logits, window
# included, only at tiny sizes on the CPU and without the flash kernel
# (tests/benchmark/test_references.py); a check of a later step's loss against
# the reference at the parameters the engine then has is listed in PERF.md, Open
# questions (its tolerance has to be read on four chips first).
# The steps on the repeated batch must also bring the loss down by a tenth: at
# these depths AdamW at 1e-3 takes 10.88 to 8.50 in three steps (PR 21's 10.86 ->
# 0.003 was one layer); a step that does not train, or whose gradients are not
# reduced over the chips, does not.
LOSS_REL_TOL = 5e-4
LOSS_MUST_FALL_TO = 0.9


def logits_close(ref, other, rel_tol, routing_gaps=None, loose_tol=None, row_errors=None):
    """``other`` reproduces ``ref`` (rows of float32 logits). Returns
    ``(ok, detail)``: finite, every row within its tolerance, and the same
    greedy token wherever ``ref``'s own top-2 margin is outside it (inside the
    margin either token is a right answer). ``routing_gaps`` (one per row, from a
    sparse model's reference) marks the rows held to the loose tolerance:
    ``loose_tol``, or ``TOSS_UP_TOL_FACTOR`` x ``rel_tol``. A list passed as
    ``row_errors`` receives ``(error / largest logit, loose)`` of every row."""
    ref, other = np.asarray(ref, np.float32), np.asarray(other, np.float32)
    if ref.shape != other.shape:
        return False, f"shapes differ: {ref.shape} vs {other.shape}"
    if not (np.isfinite(ref).all() and np.isfinite(other).all()):
        return False, "non-finite logits"
    scale = float(np.abs(ref).max())
    tol = np.full(ref.shape[0], rel_tol * scale)
    loose_tol = rel_tol * TOSS_UP_TOL_FACTOR if loose_tol is None else loose_tol
    if routing_gaps is not None:
        tol[np.asarray(routing_gaps) < ROUTING_TOSS_UP_GAP] = loose_tol * scale
    worst = np.abs(ref - other).max(axis=-1)
    if row_errors is not None:
        row_errors.extend(zip((worst / scale).tolist(), (tol > rel_tol * scale).tolist()))
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * tol
    same = ref.argmax(-1) == other.argmax(-1)
    loose = int((tol > rel_tol * scale).sum())
    detail = (f"|dlogit| by row, as log2 of the largest logit ({scale:.4g}): "
              f"{[round(float(np.log2(max(w, 1e-12) / scale)), 1) for w in worst]}; tolerance "
              f"2^{np.log2(rel_tol):.1f}" + (f", {loose_tol / rel_tol:g} x that for the {loose} "
                                            f"rows at a routing toss-up" if loose else "")
              + f"; greedy tokens equal at {int(same.sum())}/{same.size}, "
              f"{int((~decided).sum())} inside the margin")
    return bool((worst <= tol).all() and same[decided].all()), detail


def token_decided(ref_row, token, rel_tol, scale=None):
    """A greedy ``token`` is right against one reference row: it is the
    reference's argmax, or the reference's margin is inside the tolerance."""
    ref_row = np.asarray(ref_row, np.float32)
    tol = rel_tol * float(np.abs(ref_row).max() if scale is None else scale)
    return bool(ref_row.max() - ref_row[int(token)] <= 2 * tol)


def loss_close(ref, other, rel_tol=LOSS_REL_TOL):
    ok = bool(np.isfinite(ref) and np.isfinite(other) and abs(ref - other) <= rel_tol * abs(ref))
    return ok, f"loss {other:.6g} vs reference {ref:.6g} (tolerance {rel_tol:g} relative)"
