"""`python -m flexflow_tpu script.py [flags]` — script runner.

Analogue of the reference's ``flexflow_python`` embedded interpreter
(reference: python/main.cc + python/flexflow/core/flexflow_top.py:164-219,
which runs the user script inside a Legion top-level task).  Here no
special interpreter is needed; this entry strips the Legion-style
``-ll:*``/``-lg:*`` flags (flexflow_top.py:51-58 analogue), applies the
device-count ones, and runs the script.
"""

import runpy
import sys


def main():
    argv = sys.argv[1:]
    if not argv:
        print("usage: python -m flexflow_tpu <script.py> [args...]")
        return 1
    script = argv[0]
    # Filter Legion-style flags out of the script's argv but keep them
    # available to FFConfig.parse_args via the full list.
    passthrough = []
    i = 1
    while i < len(argv):
        a = argv[i]
        if a in ("-ll:tpu", "-ll:gpu", "-ll:cpu", "-ll:util", "-ll:py",
                 "-ll:fsize", "-ll:zsize", "-lg:prof"):
            i += 2
            continue
        passthrough.append(a)
        i += 1
    # The script's own argparse sees only the filtered list; the full
    # flag set stays reachable for FFConfig.parse_args(None) via the
    # config-module stash (``python -m flexflow_tpu`` has already
    # imported the package, so this costs nothing extra).
    from . import config as _config
    from .utils.compile_cache import enable_compile_cache

    _config.set_runner_argv(argv[1:])
    enable_compile_cache()
    sys.argv = [script] + passthrough
    runpy.run_path(script, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
