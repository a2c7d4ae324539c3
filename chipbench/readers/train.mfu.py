"""Forward plus backward FLOPs per image (conv by conv, chipbench/flops.py)
times the images per second of the steps the traced run finished BEFORE its
capture began, over ``count`` times the bf16 peak.  (Under the profiler a
step took twice as long on the chip, and starting and stopping the profiler
stalls the loop: neither the traced steps nor the run's whole window give
the step's own rate.)"""

from chipbench import flops, peaks


def read(ctx):
    rate = ctx["train"].get("untraced_images_per_s")
    if not rate:
        return None
    m = ctx["cell"].config["model"]
    per_image = flops.resnet_train_flops(image=m["image_size"], width=m["width"], stages=tuple(m["stage_sizes"]), classes=m["num_classes"])
    peak = peaks.peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * per_image * rate / (ctx["device"]["count"] * peak)
