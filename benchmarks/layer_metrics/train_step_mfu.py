"""Share of the bf16 peak that the traced fit's model FLOPs make over
its whole wall: the model's FLOPs for the fit's steps (six times the
parameters a token touches plus the causal score and value products, a
window layer reckoned at its window; recomputation not counted:
``ops_and_bytes``) over the peak over the traced fit's wall, set-up of
the fit (model, optimizer state, stream) included."""


def read(m):
    t, work = m["trace"], m.get("work") or {}
    if not t or m["peaks"] is None or "train_flops_per_fit" not in work:
        return None
    fits = m["facts"].get("traced_fits") or 1
    least_s = fits * work["train_flops_per_fit"] / m["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / t["window_s"]
