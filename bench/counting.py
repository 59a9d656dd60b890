"""Operations and bytes of the work the benchmark measures, from shapes.

Everything here is arithmetic on shapes, so it is checked on the CPU:

* the CNN's model FLOPs per sample (forward; training counts 3x);
* the control-plane decision's required HBM bytes;
* each Pallas kernel call's operand and result bytes and its FLOPs, read
  from the compiled program's own custom calls, so the count follows the
  shapes the program really uses.
"""
from __future__ import annotations

import base64
import re

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8}

_SHAPE = re.compile(r"\b(pred|[su]\d+|bf16|f\d+)\[([0-9,]*)\]")


def cnn_forward_flops(model: dict) -> int:
    """FLOPs of one sample's forward pass: 2 per multiply-add of the two
    3x3 SAME convolutions and the two dense layers (bias, ReLU and pooling
    are left out, so this is a lower bound)."""
    h, w, c = model["height"], model["width"], model["channels"]
    c1, c2, hid, ncls = model["c1"], model["c2"], model["hidden"], \
        model["n_classes"]
    conv1 = h * w * 9 * c * c1
    conv2 = (h // 2) * (w // 2) * 9 * c1 * c2
    flat = (h // 4) * (w // 4) * c2
    return 2 * (conv1 + conv2 + flat * hid + hid * ncls)


def client_round_flops(model: dict, samples: int, batch_size: int,
                       epochs: int) -> int:
    """Useful FLOPs of one client's local SGD in a round: forward and
    backward (3x forward) over the samples that fill whole batches."""
    used = (samples // batch_size) * batch_size
    return 3 * cnn_forward_flops(model) * used * epochs


def decision_bytes(n_users: int, n_bs: int, plane_itemsize: int) -> int:
    """The least HBM traffic of one decision: the [N, M] SNR and Eq. (11)
    coefficient planes written once and read once at their storage width,
    plus the O(N) state (positions read and written, compute times and the
    scheduled mask written and read)."""
    planes = 2 * 2 * n_users * n_bs * plane_itemsize
    state = n_users * (2 * 2 * 4 + 2 * 4 + 2 * 1)
    return planes + state


def shapes(text: str) -> list:
    """[(dtype, dims)] of every array shape written in ``text``."""
    out = []
    for dt, dims in _SHAPE.findall(text):
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def nbytes(shape_list) -> int:
    total = 0
    for dt, dims in shape_list:
        n = 1
        for d in dims:
            n *= d
        total += n * ITEMSIZE[dt]
    return total


def _kernel_flops(kernel: str, operands: list) -> int:
    """A lower bound of the arithmetic one call does, from its operands."""
    def size(i):
        n = 1
        for d in operands[i][1]:
            n *= d
        return n
    if kernel == "_fedavg_kernel":        # w [N, 1], x [N, D]: w.x
        return 2 * size(1)
    if kernel == "_segment_kernel":       # w [N, M], x [N, D]: w.T @ x
        (n, m), d = operands[0][1], operands[1][1][1]
        return 2 * n * m * d
    if kernel in ("_select_kernel", "_rowmax_kernel"):   # a compare each
        return size(0)
    if kernel == "_bw_kernel":            # one f, f' evaluation per entry
        return 4 * size(0)
    return 0


def kernel_calls(hlo_text: str, kernels) -> dict:
    """{instruction name: {"kernel", "bytes", "flops"}} for every Pallas
    custom call of a compiled program that runs one of ``kernels``."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(",
                     line)
        if not m:
            continue
        name, result = m.group(1), m.group(2)
        ops = re.search(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=",
                        line)
        operands = shapes(ops.group(1)) if ops else []
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        found = []
        if body:
            found = sorted({k.decode() for k in re.findall(
                rb"_\w*kernel", base64.b64decode(body.group(1)))})
        found = [k for k in found if k in kernels] or found
        if not found:
            continue
        # a Mosaic body may name more than one kernel function (the
        # selection call's names both of select_topk's): the call counts
        # as each of them it names, and under the first for its numbers
        kernel = found[0]
        out[name] = {"kernel": kernel, "kernels": found,
                     "bytes": nbytes(operands) + nbytes(shapes(result)),
                     "flops": _kernel_flops(kernel, operands)}
    return out


def roofline_seconds(flops: float, nbytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes_ / peaks["hbm_bytes_per_s"])


def roofline_share(ctx, kernels) -> float | None:
    """The share of their roofline (%) that the calls of ``kernels``
    reached in a traced window: each call's least time (its FLOPs and
    bytes, counted once per call from the compiled program) summed over
    its traced calls, over the calls' traced time.  A kernel that re-reads
    its operands is counted once, so the share is a lower bound.  None
    where the run was not traced or no call of ``kernels`` ran in it."""
    if ctx.trace is None:
        return None
    least = spent = 0.0
    for inst, info in ctx.kernel_calls.items():
        if info["kernel"] not in kernels:
            continue
        n = ctx.trace.call_counts.get(inst, 0)
        least += n * roofline_seconds(info["flops"], info["bytes"], ctx.peaks)
        spent += ctx.trace.call_seconds.get(inst, 0.0)
    if spent <= 0.0:
        return None
    return 100.0 * least / spent
