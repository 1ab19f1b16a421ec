"""Operations and bytes the ``resnet50-train`` step needs, from shapes.

Counted for the algorithm, whatever implements it: every convolution and
the head as matrix products, 2 FLOPs a multiply-accumulate. A training
step needs three products a layer (forward, input gradient, weight
gradient), except that the stem's input, the image, needs no gradient.
BatchNorm, ReLU, pooling and Adam are elementwise and are not counted, so
the shares computed from these counts are lower bounds of the matrix
unit's use, never above it.
"""

from __future__ import annotations

from benchmarks.reference.resnet50 import conv_table


def conv_macs(cfg: dict) -> dict:
    """Multiply-accumulates per image of every convolution, forward."""
    return {name: kh * kw * cin * cout * h * h
            for name, kh, kw, cin, cout, _s, h in conv_table(cfg)}


def head_macs(cfg: dict) -> int:
    return cfg["stages"][-1][2] * cfg["num_classes"]


def forward_flops_per_image(cfg: dict) -> float:
    return 2.0 * (sum(conv_macs(cfg).values()) + head_macs(cfg))


def conv_train_flops_per_image(cfg: dict) -> float:
    """Forward, input-gradient and weight-gradient products of the
    convolutions alone (the kernels ``conv_roofline`` is about)."""
    macs = conv_macs(cfg)
    return 2.0 * (3 * sum(macs.values()) - macs["stem"])


def train_flops_per_image(cfg: dict) -> float:
    """The whole step: the convolutions and the head."""
    return conv_train_flops_per_image(cfg) + 2.0 * 3 * head_macs(cfg)


def conv_train_least_seconds(cfg: dict, batch: int, peak: dict) -> dict:
    """The least time one chip could take for a step's convolutions: per
    product the larger of FLOPs over the bf16 peak and bytes over the HBM
    peak (operands and result once each, in the compute type), summed."""
    width = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    flops_s = bytes_s = least = 0.0
    for name, kh, kw, cin, cout, stride, h in conv_table(cfg):
        h_in = h * stride
        if name == "stem" and cfg["stem"]["space_to_depth"]:
            h_in = h + 3  # the padded space-to-depth image
        macs = kh * kw * cin * cout * h * h * batch
        x = batch * h_in * h_in * cin * width
        y = batch * h * h * cout * width
        w = kh * kw * cin * cout * width
        passes = 2 if name == "stem" else 3
        f = passes * 2.0 * macs / peak["bf16_flops_per_s"]
        b = passes * (x + y + w) / peak["hbm_bytes_per_s"]
        flops_s += f
        bytes_s += b
        least += (max(2.0 * macs / peak["bf16_flops_per_s"],
                      (x + y + w) / peak["hbm_bytes_per_s"]) * passes)
    return {"least_s": least, "flops_s": flops_s, "bytes_s": bytes_s,
            "bound": "compute" if flops_s >= bytes_s else "memory"}


# --- what the share readers ask (readers/work_share.py) --------------------

def _steps_traced(obs, params) -> float:
    """Step programs in the traced window, a run cut by an edge counted
    by the part of it inside."""
    from benchmarks.readers.program_time import runs_of

    runs = runs_of(obs, params)
    if not runs:
        return 0.0
    whole = [b - a for a, b in runs]
    mean = sorted(whole)[len(whole) // 2]
    return sum(whole) / mean


def step_mfu(ctx, obs, params):
    """The whole step's share of the chips' bf16 peak: FLOPs the steps of
    the traced window need, over the window."""
    steps = _steps_traced(obs, params)
    if not steps:
        return None
    w = obs["window"]
    flops = steps * w["batch"] * train_flops_per_image(ctx.config)
    least = flops / (w["chips"] * ctx.peak["bf16_flops_per_s"])
    return least, obs["trace"]["window_s"]


def conv_roofline(ctx, obs, params):
    """The convolutions' least time over the summed device time of the
    operations that compute them (``params["op_patterns"]``, on the
    fullest-used chip, which holds its own share of the batch)."""
    from benchmarks import trace_reduce

    steps = _steps_traced(obs, params)
    taken = trace_reduce.op_seconds(obs["trace"], params["op_patterns"])
    if not steps or not taken:
        return None
    w = obs["window"]
    least = conv_train_least_seconds(ctx.config, w["batch"] // w["chips"],
                                     ctx.peak)
    return steps * least["least_s"], taken
