from repro.analysis.hlo import collective_bytes, hlo_collectives
from repro.analysis.roofline import RooflineTerms, roofline_from_compiled, HW
from repro.analysis.flops import sdkde_flops, sdkde_bytes

__all__ = [
    "collective_bytes",
    "hlo_collectives",
    "RooflineTerms",
    "roofline_from_compiled",
    "HW",
    "sdkde_flops",
    "sdkde_bytes",
]
