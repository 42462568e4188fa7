import sys

from repro.bench import compile_cache
from repro.bench.cli import main

compile_cache.enable()
sys.exit(main())
