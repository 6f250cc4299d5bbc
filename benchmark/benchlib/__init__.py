"""The benchmark's own code: nothing here imports `minio_tpu` except
`node.py`, the one seam through which the harness boots and reads the
system under test."""
