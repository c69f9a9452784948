"""Repo-root launcher shim: ``python -m launch.serve``.

Makes the ``src/repro/launch`` entry points runnable from the repository
root without exporting PYTHONPATH — the submodule here adds ``src`` to
``sys.path`` and delegates to the real ``repro.launch`` module.
"""
import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
