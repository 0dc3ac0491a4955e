"""Model FLOPs per step (``flops.gcn_train_step``) over the window's time
per step, as a percentage of the chip's bf16 peak."""


def read(ctx):
    if ctx["kind"] != "train" or not ctx["peaks"]:
        return None
    return (100.0 * ctx["flops_per_step"] / ctx["window"]["step_s"]
            / ctx["peaks"]["flops_bf16"])
