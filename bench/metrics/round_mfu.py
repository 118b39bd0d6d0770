"""round_mfu (%): model FLOPs of the clients' forward and backward passes
(bench/counts.py client_flops, recomputation not counted) times the
rounds traced, over the traced window times the chips times the chip's
bf16 peak. It bounds what any kernel's gain can show end to end."""


def read(view):
    f = view.facts
    if not f["rounds"] or view.window_s <= 0:
        return None
    return 100.0 * f["flops_round"] * f["rounds"] / (
        view.window_s * f["chips"] * f["peak"]["bf16_flops"])
