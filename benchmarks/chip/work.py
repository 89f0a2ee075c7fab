"""Operations and bytes the algorithm needs, counted from the configuration.

The counts are of the work itself, not of any implementation: a later change
to how a step is computed reads against the same numbers. All
arrays are float32 (4 bytes a value), as the configurations state.

Training, per sample (one coordinate of one rank's batch):

- FLOPs: the MLP's matmuls forward (2 * din * dout per layer) and backward
  (twice the forward: the weight and the input cotangents), plus the
  encoding's trilinear blend forward (a multiply-add per corner, level and
  feature) and its gradient backward (the same again). Recomputation and
  the coordinate draws are not counted.
- bytes: the 8 corner rows of every level read forward and their gradients
  written backward (8 * L * F values each way), and the 8 target voxels.

Per rank and step: the parameters and both Adam moments read and written
once (6 values a parameter).
"""
from __future__ import annotations

WORD = 4                       # bytes of a float32 value


def mlp_dims(model: dict) -> list[int]:
    L, F = model["n_levels"], model["n_features_per_level"]
    W, H = model["n_neurons"], model["n_hidden_layers"]
    return [L * F] + [W] * H + [model.get("out_dim", 1)]


def param_count(model: dict) -> int:
    L, F = model["n_levels"], model["n_features_per_level"]
    T = 1 << model["log2_hashmap_size"]
    dims = mlp_dims(model)
    return L * T * F + sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def train_flops_per_sample(model: dict) -> int:
    dims = mlp_dims(model)
    mlp_fwd = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    blend = 2 * 8 * model["n_levels"] * model["n_features_per_level"]
    return 3 * mlp_fwd + 2 * blend


def train_bytes_per_sample(model: dict) -> int:
    rows = 8 * model["n_levels"] * model["n_features_per_level"]
    return (2 * rows + 8) * WORD


def train_bytes_per_rank_step(model: dict) -> int:
    return 6 * param_count(model) * WORD


def train_step_work(model: dict, ranks: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one training step of ``ranks`` ranks."""
    n = model["batch_size"]
    flops = ranks * n * train_flops_per_sample(model)
    nbytes = ranks * (n * train_bytes_per_sample(model)
                      + train_bytes_per_rank_step(model))
    return float(flops), float(nbytes)


def roofline(flops: float, nbytes: float, seconds: float, peaks,
             chips: int) -> tuple[float, str]:
    """(% of the roofline, the bound) of work that took ``seconds`` of device
    time on each of ``chips`` chips: the least time the chips could take, the
    larger of FLOPs over peak FLOP/s and bytes over peak bandwidth, over the
    time taken."""
    t_flops = flops / (chips * peaks.bf16_flops)
    t_bytes = nbytes / (chips * peaks.hbm_bytes_per_s)
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
