"""Images per second times the training FLOPs per image (forward, weight
grads, input grads but the first layer's), over chips times the bf16
peak."""


def read(run):
    step_s = run.e2e.get("train_step_s")
    if not step_s:
        return None
    chips = len(run.devices)
    return 100.0 * run.obs["train_flops"] / (step_s * chips * run.peaks["bf16_flops"])
