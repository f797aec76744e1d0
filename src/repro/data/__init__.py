from repro.data.density import DensityWeighting, density_weights

__all__ = [
    "DensityWeighting",
    "density_weights",
]
