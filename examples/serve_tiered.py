"""Serving example: batched decode with the RARO-tiered KV cache (the
paper's technique as a TPU serving feature, DESIGN.md §2B).

Decodes a batch of sequences with the Pallas tiered-attention kernel
(compiled on a TPU, interpreted elsewhere), RARO promoting hot pages to
bf16 and demoting cold ones to int4, then compares against static
all-int4:

  PYTHONPATH=src python examples/serve_tiered.py --steps 64 --batch 4
"""

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    enable_compile_cache()
    main()
