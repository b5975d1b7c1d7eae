"""The plain reference for `jobs/keyed_window_mesh.py`.  Sharding the state
over chips changes no answer, so the semantics are `reference/keyed_window.py`'s
numpy group-by, letter for letter.  Imports nothing of the program."""

from __future__ import annotations

from reference.keyed_window import Reference  # noqa: F401
