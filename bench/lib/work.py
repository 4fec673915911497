"""The work a step of the model requires, from the configuration's shapes.

These counts are the model's, not any kernel's: they stay the same whatever
code later does the step.  A multiply-accumulate counts once per weight that
the step has to apply:

* layer 0: the input matrix once per frame (its stimulus is shared by the
  time steps) and the recurrent matrix once per time step;
* layer 1: the feed-forward and the recurrent matrix once per time step;
* the readout: once per kept weight, over the merged spikes of all time
  steps.

An operation is two per multiply-accumulate.  Bytes per step are the
weights at their stored width (plus one float32 scale per output column
where quantized), read once per step, and per slot: the input frame, the
recurrent state read and written, and the logits written.
"""

from __future__ import annotations


def kept_fc(model: dict, compression: dict) -> int:
    n = model["hidden_dim"] * model["fc_dim"]
    frac = float(compression.get("fc_prune_frac") or 0.0)
    return max(int(round(n * (1.0 - frac))), 1) if frac > 0.0 else n


def macs_per_frame(model: dict, compression: dict) -> int:
    d, h, ts = model["input_dim"], model["hidden_dim"], model["num_ts"]
    return d * h + 3 * ts * h * h + kept_fc(model, compression)


def ops_per_frame(model: dict, compression: dict) -> int:
    return 2 * macs_per_frame(model, compression)


def weight_bytes(model: dict, compression: dict) -> float:
    d, h, c = model["input_dim"], model["hidden_dim"], model["fc_dim"]
    bits = compression.get("weight_bits") or 32
    entries = d * h + 3 * h * h + kept_fc(model, compression)
    scales = 4 * (4 * h + c) if compression.get("weight_bits") else 0
    return entries * bits / 8.0 + scales


def bytes_per_slot_frame(model: dict) -> int:
    """Input frame, recurrent state in and out, logits out (float32)."""
    d, h, c, ts = (model["input_dim"], model["hidden_dim"], model["fc_dim"],
                   model["num_ts"])
    state = 2 * (ts * h) + 4 * h  # spikes of both layers, membranes, carries
    return 4 * (d + 2 * state + c)


def bytes_per_step(model: dict, compression: dict, slots: int) -> float:
    return weight_bytes(model, compression) + slots * bytes_per_slot_frame(
        model)


def least_step_seconds(model: dict, compression: dict, slots: int,
                       peak_ops: float, peak_bytes_per_s: float) -> tuple:
    """(seconds, bound) of the fastest step the chip could make over
    ``slots`` slot-frames: the larger of operations over the peak rate and
    bytes over the memory bandwidth."""
    t_ops = slots * ops_per_frame(model, compression) / peak_ops
    t_mem = bytes_per_step(model, compression, slots) / peak_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
