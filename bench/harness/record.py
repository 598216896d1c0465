"""What a run recorded, in the form the per-layer readers and the kernel
cost functions take: the host spans of the traced interval, the device
trace's reduction, and the cell's shapes; what is model-specific in a
count the configuration's family answers (``Run.family``)."""
from __future__ import annotations

import dataclasses
import importlib.util

from harness import arch
from harness.cell import BENCH


@dataclasses.dataclass
class Run:
    config: dict
    dims: object             # the family's Dims
    batch: int
    chips: int
    page_size: int
    page_bytes: float        # the pool's bytes per page of one layer's rows
    t0: float                # traced interval, host clock
    t1: float
    all_spans: list
    trace: object            # harness.trace.DeviceTrace or None
    peaks: dict

    @property
    def family(self):
        """The configuration's model family (``harness.arch``)."""
        return arch.family_of(self.config)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def spans(self, name: str):
        return [s for s in self.all_spans
                if s.name == name and s.t0 >= self.t0 and s.t1 <= self.t1]

    def segments(self):
        """``(pos0, remaining, steps)`` of each fused segment, live slots
        only."""
        return [(s.attrs["pos0"], s.attrs["remaining"], s.attrs["steps"])
                for s in self.spans("segment")]

    def prefills(self):
        """``(rows, bucket)`` of each prefill the engine was handed."""
        return [tuple(shape) for s in self.spans("admit")
                for shape in s.attrs["computed"]]

    def prompt_lengths(self):
        return [n for s in self.spans("admit") for n in s.attrs["prompts"]]

    def steps(self):
        """``(rows, logit_rows, count)``: the token rows each model step is
        given, and how many such steps ran."""
        out = [(B * S, B, 1) for B, S in self.prefills()]
        out += [(self.batch, self.batch, n) for _, _, n in self.segments()]
        return out


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel(name: str):
    """The cost module ``bench/kernels/<name>.py``."""
    return _load(BENCH / "kernels" / f"{name}.py")


def reader(metric: str):
    """The reader ``bench/metrics/<metric>.py``."""
    return _load(BENCH / "metrics" / f"{metric}.py")


def roofline_share(run: Run, name: str):
    """Percent of the roofline bound that kernel ``name`` reached in the
    traced interval: the least time its operations and bytes allow at the
    chip's peaks, over its device time.  None where the trace holds none of
    its calls or the kernel does not run in this configuration."""
    if run.trace is None:
        return None
    k = kernel(name)
    t = run.trace.kernel_time(k.TRACE)
    c = k.cost(run)
    if not t or c is None:
        return None
    ops, byts = c
    t_min = max(ops / (run.peaks[k.PEAK] * run.chips),
                byts / (run.peaks["hbm_bytes_per_s"] * run.chips))
    return 100.0 * t_min / t


def mfu(run: Run):
    """Percent of the chips' bf16 peak that the window's useful model FLOPs
    make over the traced interval."""
    flops = kernel("model_step").useful_flops(run)
    if not flops:
        return None
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["bf16_flops"])


def read_all(run: Run, names) -> dict:
    """Each named metric's reading; a reader that finds nothing is left
    out."""
    out = {}
    for n in names:
        v = reader(n).read(run)
        if v is not None:
            out[n] = v
    return out
