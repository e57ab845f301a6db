"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

The window is the host span ``bench.window`` that the runner opens
around the measured loop.  On each device plane the operations (the line
of XLA ops) are clipped to the window; busy time is the union of their
intervals, idle share one minus busy over the window.  Programs are the
events of the XLA modules line; an op event is named by the HLO
instruction it ran (a TPU trace gives the whole instruction text, of
which the name is kept) and belongs to the program run that encloses
it.  A Pallas kernel is known by the
function name that its Mosaic body records in the compiled HLO
(for instance ``_fwd_kernel`` of ``kernels/flash_kernel.py``): the
HLO texts of the programs that ran give, for each custom call, its
kernel and its operand shapes.  An idle gap is named by the innermost
of the benchmark's own host spans (``bench.*``) open across its middle.
"""
from __future__ import annotations

import base64
import bisect
import dataclasses
import glob
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
_KERNEL_FN = re.compile(rb"[A-Za-z0-9_]*kernel")
_SHAPE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred|f8e4m3fn)\[([\d,]*)\]")


@dataclasses.dataclass
class Op:
    device: int
    name: str
    module: str
    start: int       # ns
    end: int         # ns


@dataclasses.dataclass
class Kernel:
    """One Pallas custom call of a compiled program."""

    kernel: str               # the kernel function's name
    operands: List[Tuple[int, ...]]
    results: List[Tuple[int, ...]]


def instruction_name(name: str) -> str:
    """``%fusion.3 = bf16[2,256] fusion(...)`` -> ``fusion.3``; a bare
    name is kept as it is."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def module_name(name: str) -> str:
    """A program's name without the run id a trace appends
    (``jit_step(12)`` -> ``jit_step``)."""
    return re.sub(r"\(\d+\)$", "", name)


def kernels_in_hlo(text: str) -> Dict[Tuple[str, str], Kernel]:
    """{(program, instruction name): Kernel} for every
    ``tpu_custom_call`` of one compiled program."""
    m = re.match(r"HloModule ([^\s,]+)", text)
    module = m.group(1) if m else ""
    out = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split("=", 1)[0].strip().lstrip("%").split()[-1]
        m = re.search(r'"body":"([^"]*)"', line)
        kernel = _kernel_name(base64.b64decode(m.group(1))) if m else None
        head = line.split("custom-call(", 1)[0]
        out[(module, name)] = Kernel(
            kernel=kernel or "unknown",
            operands=_shapes(_braced(line, "operand_layout_constraints=")),
            results=_shapes(head.split("=", 1)[1] if "=" in head else ""))
    return out


def _braced(line: str, key: str) -> str:
    """The balanced ``{...}`` group that follows ``key`` in ``line``."""
    i = line.find(key)
    if i < 0:
        return ""
    i += len(key)
    depth = 0
    for j in range(i, len(line)):
        depth += {"{": 1, "}": -1}.get(line[j], 0)
        if depth == 0:
            return line[i:j + 1]
    return line[i:]


def _shapes(text: str) -> List[Tuple[int, ...]]:
    return [tuple(int(d) for d in dims.split(",") if d)
            for _, dims in _SHAPE.findall(text)]


def _kernel_name(body: bytes) -> Optional[str]:
    """The name of the kernel function a Mosaic module was built from.

    The module's strings are NUL-separated: attribute and operation
    names, source file paths, and the functions the kernel's operations
    come from.  The kernel function is the first of them whose name
    ends in ``kernel`` (``_fwd_kernel``, ``_paged_kernel``); the program's
    kernel functions have names unique across its kernel files.
    """
    for tok in body.split(b"\x00"):
        if _KERNEL_FN.fullmatch(tok):
            return tok.decode()
    return None


@dataclasses.dataclass
class Summary:
    window: Tuple[int, int]
    n_devices: int
    ops: List[Op]
    modules: List[Op]
    spans: List[Tuple[str, int, int]]        # host spans (name, start, end)
    kernels: Dict[Tuple[str, str], Kernel]   # (program, instruction)
    hlo_texts: List[str] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        busy = [_union(sorted((o.start, o.end) for o in self.ops
                              if o.device == dev))
                for dev in range(self.n_devices)]
        return sum(busy) / 1e9 / self.n_devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_of(self, op: Op) -> Optional[Kernel]:
        return self.kernels.get((module_name(op.module), op.name))

    def kernel_ops(self, kernel: str) -> List[Tuple[Op, Kernel]]:
        """The device runs of ``kernel``, matched by the program and the
        instruction name of each op."""
        out = []
        for o in self.ops:
            k = self.kernel_of(o)
            if k is not None and k.kernel == kernel:
                out.append((o, k))
        return out

    def module_runs(self, part: str) -> List[Op]:
        """Runs of the programs whose name holds ``part``."""
        return [m for m in self.modules if part in m.module]

    def idle_gaps(self, device: int = 0) -> List[Tuple[int, int]]:
        ivs = sorted((o.start, o.end) for o in self.ops
                     if o.device == device)
        gaps, t = [], self.window[0]
        for s, e in ivs:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        return gaps

    def span_at(self, t: int) -> str:
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and name != WINDOW_SPAN \
                    and name.startswith(SPAN_PREFIX):
                if best is None or e - s < best[2] - best[1]:
                    best = (name, s, e)
        return best[0] if best else "no host span"

    def breakdown(self, top: int = 10) -> Dict:
        tot: Dict[str, float] = {}
        for o in self.ops:
            k = self.kernel_of(o)
            label = k.kernel if k else f"{o.module}/{o.name}"
            tot[label] = tot.get(label, 0.0) + (o.end - o.start) / 1e9
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return dict(device_ops=[[n, s / self.n_devices] for n, s in ops],
                    idle_gaps=[[self.span_at((s + e) // 2), (e - s) / 1e9]
                               for s, e in gaps])


def _union(ivs: List[Tuple[int, int]]) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in ivs:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _stat(ev, key: str):
    try:
        for k, v in ev.stats:
            if k == key:
                return v
    except Exception:  # events without readable stats
        return None
    return None


def _device_index(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:TPU:(\d+)(?:\s|$)", plane_name)
    return int(m.group(1)) if m else None


def reduce(trace_dir: str, hlo_texts: List[str], n_devices: int) -> Summary:
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    return reduce_profile(ProfileData.from_file(path), hlo_texts, n_devices)


def reduce_profile(pd, hlo_texts: List[str], n_devices: int) -> Summary:
    spans, ops, modules = [], [], []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is None:
                if plane.name.startswith("/host:"):
                    spans.extend((e.name, int(e.start_ns), int(e.end_ns))
                                 for e in line.events)
                continue
            if dev >= n_devices:
                continue
            if line.name in OP_LINES:
                ops.extend(Op(dev, instruction_name(e.name),
                              str(_stat(e, "hlo_module") or ""),
                              int(e.start_ns), int(e.end_ns))
                           for e in line.events)
            elif line.name in MODULE_LINES:
                modules.extend(Op(dev, e.name, e.name, int(e.start_ns),
                                  int(e.end_ns)) for e in line.events)
    _name_programs(ops, modules)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    lo, hi = win[0] if win else (min(o.start for o in ops),
                                 max(o.end for o in ops))
    ops = [_clip(o, lo, hi) for o in ops if o.end > lo and o.start < hi]
    modules = [m for m in modules if m.end > lo and m.start < hi]
    kernels = {}
    for text in hlo_texts:
        kernels.update(kernels_in_hlo(text))
    return Summary(window=(lo, hi), n_devices=n_devices, ops=ops,
                   modules=modules, spans=spans, kernels=kernels,
                   hlo_texts=list(hlo_texts))


def _name_programs(ops: List[Op], modules: List[Op]) -> None:
    """An op whose event carries no program name takes that of the
    program run on its device that encloses it."""
    runs = {}
    for m in sorted(modules, key=lambda m: m.start):
        runs.setdefault(m.device, []).append(m)
    starts = {d: [m.start for m in ms] for d, ms in runs.items()}
    for o in ops:
        if o.module or o.device not in runs:
            continue
        i = bisect.bisect_right(starts[o.device], o.start) - 1
        if i >= 0 and runs[o.device][i].end >= o.end:
            o.module = module_name(runs[o.device][i].module)


def _clip(o: Op, lo: int, hi: int) -> Op:
    return dataclasses.replace(o, start=max(o.start, lo), end=min(o.end, hi))


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader is given."""

    run: object
    counters: Dict
    summary: Summary
