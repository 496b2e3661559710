"""The chip benchmark of ray_tpu: see chipbench/README.md."""
