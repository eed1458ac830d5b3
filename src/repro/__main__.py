"""``python -m repro`` — the quantization pipeline CLI (pipeline/cli.py)."""
import sys

from .launch.compile_cache import enable_compile_cache
from .pipeline.cli import main

if __name__ == "__main__":
    # the process entry, not main(): tests call main() in-process and must
    # not switch a persistent cache on for the rest of their worker
    enable_compile_cache()
    sys.exit(main())
