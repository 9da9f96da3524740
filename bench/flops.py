"""Operations and bytes of the benchmark's models, computed from shapes.

Every count is the work the algorithm requires, independent of how the
program pads, fuses or recomputes it.  A multiply-add is 2 FLOPs.
"""

from __future__ import annotations

RESNET50_STAGES = (3, 4, 6, 3)


def dense_train_flops(m: int, k: int, n: int, input_grad: bool = True) -> int:
    """Forward and backward of ``[m,k] @ [k,n]``: the forward product, the
    weight gradient and (unless the input is a constant) the input
    gradient."""
    fwd = 2 * m * k * n
    return fwd * (3 if input_grad else 2)


def mlp_layers(width: int, depth: int, out: int) -> list[tuple[int, int]]:
    """(fan_in, fan_out) of the QuadConv filter MLP, R^3 -> R^{out}."""
    sizes = (3,) + (width,) * (depth - 1) + (out,)
    return list(zip(sizes[:-1], sizes[1:]))


def quadconv_layers(cfg: dict) -> list[dict]:
    """The autoencoder's QuadConv layers: points J = I and channels C -> O."""
    n, pool, blocks = cfg["n_points"], cfg["pool"], cfg["blocks"]
    internal, channels = cfg["internal"], cfg["channels"]
    layers, c = [], channels
    for b in range(blocks):
        layers.append({"points": n // pool ** b, "c_in": c, "c_out": internal})
        c = internal
    for b in range(blocks):
        pts = n // pool ** (blocks - b - 1)
        layers.append({"points": pts, "c_in": internal, "c_out": internal})
    return layers


def quadconv_contract_flops(batch: int, points: int, c_in: int,
                            c_out: int) -> int:
    """out[b,j,o] = sum_{i,c} w[i] G[j,i,o,c] f[b,i,c] (J = I = points)."""
    return 2 * batch * points * points * c_in * c_out


def quadconv_contract_bytes(batch: int, points: int, c_in: int, c_out: int,
                            itemsize: int = 4) -> int:
    """Least HBM traffic of one contraction: read f, w and G, write out."""
    j = i = points
    return itemsize * (batch * i * c_in + i + j * i * c_out * c_in
                       + batch * j * c_out)


def ae_forward_flops(cfg: dict, batch: int) -> int:
    """Matmul FLOPs of one autoencoder forward pass over ``batch`` samples:
    each QuadConv layer's filter MLP over its J*I offsets (shared by the
    batch) and its contraction, plus the two dense heads and the channel
    head."""
    total = 0
    for layer in quadconv_layers(cfg):
        offsets = layer["points"] ** 2
        for k, n in mlp_layers(cfg["mlp_width"], cfg["mlp_depth"],
                               layer["c_out"] * layer["c_in"]):
            total += 2 * offsets * k * n
        total += quadconv_contract_flops(batch, layer["points"],
                                         layer["c_in"], layer["c_out"])
    total += _head_flops(cfg, batch)
    return total


def _bottleneck(cfg: dict) -> int:
    return cfg["n_points"] // cfg["pool"] ** cfg["blocks"] * cfg["internal"]


def _head_flops(cfg: dict, batch: int) -> int:
    bott = _bottleneck(cfg)
    return (2 * batch * bott * cfg["latent"] * 2
            + 2 * batch * cfg["n_points"] * cfg["internal"] * cfg["channels"])


def ae_train_step_flops(cfg: dict, batch: int) -> int:
    """Forward and backward of one SGD microstep.  The filter MLP's first
    layer needs no input gradient (its input is the fixed offsets), and the
    contraction's three gradients (f, w, G) each cost one forward."""
    total = 0
    for layer in quadconv_layers(cfg):
        offsets = layer["points"] ** 2
        for idx, (k, n) in enumerate(mlp_layers(
                cfg["mlp_width"], cfg["mlp_depth"],
                layer["c_out"] * layer["c_in"])):
            total += dense_train_flops(offsets, k, n, input_grad=idx > 0)
        total += 3 * quadconv_contract_flops(batch, layer["points"],
                                             layer["c_in"], layer["c_out"])
    total += 3 * _head_flops(cfg, batch)
    return total


def ae_epoch_flops(cfg: dict, gather: int, batch: int) -> int:
    """One fused epoch: ``ceil((gather-1)/batch)`` microsteps of ``batch``
    samples and one validation forward of one sample."""
    n_train = max(gather - 1, 1)
    bs = min(batch, n_train)
    steps = -(-n_train // bs)
    return steps * ae_train_step_flops(cfg, bs) + ae_forward_flops(cfg, 1)


def ae_epoch_contractions(cfg: dict, gather: int, batch: int) -> list[dict]:
    """Every forward QuadConv contraction of one fused epoch, with its
    FLOPs and least bytes (the kernel runs the forward only)."""
    n_train = max(gather - 1, 1)
    bs = min(batch, n_train)
    steps = -(-n_train // bs)
    calls = []
    for b, reps in ((bs, steps), (1, 1)):
        for layer in quadconv_layers(cfg):
            args = (b, layer["points"], layer["c_in"], layer["c_out"])
            calls += [{"flops": quadconv_contract_flops(*args),
                       "bytes": quadconv_contract_bytes(*args)}] * reps
    return calls


def resnet50_macs(image: int = 224, classes: int = 1000) -> int:
    """Multiply-adds of one ResNet-50 v1.5 forward (stride on the 3x3)."""
    def conv(hw, k, cin, cout):
        return hw * hw * k * k * cin * cout

    hw = image // 2                       # 7x7/2 stem
    macs = conv(hw, 7, 3, 64)
    hw //= 2                              # 3x3/2 max pool
    cin = 64
    for s, blocks in enumerate(RESNET50_STAGES):
        cmid = 64 * 2 ** s
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            out_hw = hw // stride
            macs += conv(hw, 1, cin, cmid)
            macs += conv(out_hw, 3, cmid, cmid)
            macs += conv(out_hw, 1, cmid, cmid * 4)
            if stride != 1 or cin != cmid * 4:
                macs += conv(out_hw, 1, cin, cmid * 4)
            cin, hw = cmid * 4, out_hw
    return macs + cin * classes


def resnet50_flops(image: int = 224, classes: int = 1000) -> int:
    return 2 * resnet50_macs(image, classes)


def gather_rows_bytes(rows: int, elem_bytes: int) -> int:
    """Least traffic of a row gather: read each row once, write it once."""
    return 2 * rows * elem_bytes


def probe_bytes(capacity: int, queries: int) -> int:
    """Least traffic of the key probe: read the slot keys and versions
    (uint32 + int32) and the queries, write one slot and one flag each."""
    return 8 * capacity + 4 * queries + 8 * queries
