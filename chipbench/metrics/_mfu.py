"""Shared arithmetic of the ``mfu.*`` readers."""


def mfu(run, kind):
    if run["kind"] != kind or not run["model_flops"]:
        return None
    return 100.0 * run["model_flops"] / run["window_s"] / run["peaks"]["bf16_flops_per_s"]
