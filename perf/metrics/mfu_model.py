"""The whole step's share of the chips' peak: model operations of forward
and backward per item, times items per second of the traced window, over
chips times the bf16 peak. Recomputation does not count."""

from perf.lib import counts


def ops_per_item(run):
    c = run.config
    if run.traffic["input"] == "tokens":
        return counts.gpt2_train_ops_per_token(c, c["n_positions"])
    if run.traffic["input"] == "images":
        return counts.resnet50_train_ops_per_image(c["image_size"],
                                                   c["num_classes"])
    return None


def read(run):
    per_item = ops_per_item(run)
    if run.trace is None or per_item is None:
        return None
    rate = run.window["items"] / run.trace.window_s
    peak = run.cell["chips"] * run.peaks()["bf16_flops"]
    return 100.0 * per_item * rate / peak
