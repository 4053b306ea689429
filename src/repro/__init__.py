"""OverSketched Newton reproduction on JAX/Pallas."""
