"""Layer geometry, operations and bytes of a configuration, from its file.

Everything here is arithmetic on the unpadded shapes a configuration's
``layers`` table states; nothing reads the program.  ``walk`` is the one
spatial walk that the weights, the reference and the roofline share.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List

BENCH_DIR = Path(__file__).resolve().parent

#: layer kinds a configuration file may use
KINDS = ("conv", "depthwise", "global_avg_pool", "dense")


@dataclasses.dataclass(frozen=True)
class Geom:
    """One layer at its real size: input (h, w, d) -> output (ho, wo, f)."""
    name: str
    kind: str
    k: int
    stride: int
    padding: str
    h: int
    w: int
    d: int
    ho: int
    wo: int
    f: int
    act: str
    bias: bool

    @property
    def contraction(self) -> int:
        """S: the length of one dot product (K*K*D; K*K for depthwise)."""
        if self.kind in ("depthwise", "global_avg_pool"):
            return self.k * self.k
        return self.k * self.k * self.d

    @property
    def macs(self) -> int:
        """Multiply-accumulates of one image."""
        return self.ho * self.wo * self.f * self.contraction

    @property
    def weight_count(self) -> int:
        n = self.f * self.contraction
        return n + (self.f if self.bias else 0)

    @property
    def on_mxu(self) -> bool:
        """SC/PC/FC layers run as a contraction kernel; depthwise and the
        pool are per-channel VPU work."""
        return self.kind in ("conv", "dense")


def load_config(name: str, configs_dir: Path = BENCH_DIR / "configs") -> dict:
    path = configs_dir / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


def walk(cfg: dict) -> List[Geom]:
    """The layers of ``cfg`` with their input and output shapes."""
    h, w, d = cfg["input_shape"]
    out: List[Geom] = []
    for spec in cfg["layers"]:
        kind = spec["kind"]
        if kind not in KINDS:
            raise ValueError(f"layer {spec['name']!r}: unknown kind {kind!r}")
        if kind == "dense":
            f = spec["units"]
            g = Geom(spec["name"], kind, 1, 1, "SAME", 1, 1, h * w * d,
                     1, 1, f, spec.get("act", "none"),
                     spec.get("bias", False))
            h, w, d = 1, 1, f
        elif kind == "global_avg_pool":
            if h != w:
                raise ValueError("the global pool needs a square map")
            g = Geom(spec["name"], kind, h, 1, "VALID", h, w, d, 1, 1, d,
                     "none", False)
            h, w = 1, 1
        else:
            k, s = spec["k"], spec["stride"]
            ho, wo = -(-h // s), -(-w // s)
            f = d if kind == "depthwise" else spec["filters"]
            # a folded BatchNorm leaves a bias behind
            g = Geom(spec["name"], kind, k, s, "SAME", h, w, d, ho, wo, f,
                     spec.get("act", "none"), spec.get("bn", False))
            h, w, d = ho, wo, f
        out.append(g)
    return out


def macs_per_image(cfg: dict) -> int:
    return sum(g.macs for g in walk(cfg))


def ops_per_image(cfg: dict) -> int:
    """2 x every layer's MACs: the operations one image needs."""
    return 2 * macs_per_image(cfg)


def param_count(cfg: dict) -> int:
    """Weights and biases, the pool's constant weights left out."""
    return sum(g.weight_count for g in walk(cfg)
               if g.kind != "global_avg_pool")


def kernel_least_time_s(g: Geom, batch: int, ops_per_s: float,
                        bytes_per_s: float) -> tuple:
    """(compute term, memory term) of one SC/PC/FC layer over ``batch``
    images, in seconds: 2*B*P*S*F over the int8 peak, and the bytes the
    layer needs (f32 input, int8 weights, f32 output) over the HBM
    bandwidth.  Unpadded shapes: padding is the kernel's own cost."""
    ops = 2 * batch * g.macs
    need = (batch * g.h * g.w * g.d * 4 + g.f * g.contraction
            + batch * g.ho * g.wo * g.f * 4)
    return ops / ops_per_s, need / bytes_per_s
