"""expert_ms_per_step: device time of the expert layer's grouped-FFN
kernel inside ``bench_decode``, per call, in ms, averaged over the chips.

The kernel's operations are found by their HLO names: the three Pallas
calls of ``repro.kernels.expert_ffn`` (megablox's ``gmm``) are named
``gmm``, and XLA numbers each instance (``gmm.11``), so the rule
``^gmm(\\.\\d+)?$`` matches them and nothing else in ``bench_decode``
(the decode step compiled for a TPU v5e names them so).  The elementwise
SiLU product between them is an XLA fusion and is not counted.  A
program without the kernel reads nothing."""
import re

KERNEL = re.compile(r"^gmm(\.\d+)?$")


def kernel_s(trace) -> float:
    """Seconds of the kernel's operations inside ``bench_decode``."""
    return sum(s for name, s in trace.ops
               if name.startswith("bench_decode/")
               and KERNEL.match(name.split("/", 1)[1]))


def read(ctx):
    calls = ctx.trace.program("bench_decode")["calls"]
    s = kernel_s(ctx.trace)
    return s / calls * 1e3 if calls and s else None
