"""repro — Arm-membench throughput benchmark, reproduced on the JAX/TPU stack."""
