"""Operations and bytes of each kernel and of a whole step, from shapes.

Counts are taken from the unpadded shapes the algorithm needs: the lane
padding the kernels add (a batch of 8 padded to 128 lanes) is not work,
so it shows as a low share of the roofline.  Bytes are what the work
moves through HBM once; an operand that XLA keeps in on-chip memory
moves less, so a share read from a very small working set can flatter.

The peak of each count comes from ``peaks.json``: integer matrix products
are held to the int8 peak, everything else to the bf16 peak (the chip's
highest float rate), and bytes to HBM bandwidth.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float
    int8: bool = False      # ops are int8 MACs (held to the int8 peak)

    def __add__(self, other: "Work") -> "Work":
        if self.int8 != other.int8:
            raise ValueError("cannot add int8 and float work")
        return Work(self.ops + other.ops, self.bytes + other.bytes, self.int8)

    def __mul__(self, k: float) -> "Work":
        return Work(self.ops * k, self.bytes * k, self.int8)


ZERO = Work(0.0, 0.0)


def peaks_for(device_kind: str) -> dict:
    """The peak row of ``device_kind``; an unknown kind is an error."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def least_time(work: Work, peaks: dict):
    """(seconds, bound): the larger of ops over peak and bytes over HBM."""
    rate = peaks["int8_ops_per_s"] if work.int8 else peaks["bf16_flops_per_s"]
    t_ops = work.ops / rate
    t_mem = work.bytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "bandwidth")


def lif_update(n: int, batch: int) -> Work:
    """One ``lif_update`` call on (n, batch) f32 maps.

    v' = i + alpha*v - z*v_th; z' = v' >= v_th: 5 operations per element;
    reads i, v, z and writes v', z', all f32: 20 bytes per element.
    """
    return Work(5.0 * n * batch, 20.0 * n * batch)


def spike_wdm_matmul(m: int, cols: int, batch: int) -> Work:
    """One int8 (m, cols) x (cols, batch) -> int32 (m, batch) product."""
    return Work(2.0 * m * cols * batch,
                m * cols + cols * batch + 4.0 * m * batch, int8=True)


def lif_parallel_scan(steps: int, features: int) -> Work:
    """One affine scan v[t] = alpha*v[t-1] + c[t] over (steps, features):
    a multiply and an add per element; reads c and writes v in f32."""
    return Work(2.0 * steps * features, 8.0 * steps * features)


#: Bytes one synapse costs when the table is read once: an int8 weight,
#: an int32 target index and an int8 delay.
SYNAPSE_BYTES = 6
#: Bytes one neuron's state costs per lane-step: its f32 membrane read and
#: written, its int8 spike read and written.
NEURON_STATE_BYTES = 10
#: Bytes one input channel costs per lane-step: its int8 spike, read.
INPUT_SPIKE_BYTES = 1


def network_step(n_synapses: int, n_neurons: int, n_input: int,
                 batch: int) -> Work:
    """One timestep of the whole network at ``batch`` lanes, whatever form
    runs it: 2 operations per synapse per lane and Eq. 1's 5 per updated
    neuron per lane; the synapse table read once, the updated neurons'
    state and the input spikes per lane.  Input channels hold no state."""
    return Work(2.0 * n_synapses * batch + 5.0 * n_neurons * batch,
                SYNAPSE_BYTES * n_synapses + NEURON_STATE_BYTES * n_neurons * batch
                + INPUT_SPIKE_BYTES * n_input * batch)
