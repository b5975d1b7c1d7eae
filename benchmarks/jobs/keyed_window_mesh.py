"""`jobs/keyed_window.py`'s job with its keyed state sharded by key group over
the chips of one host: `env.set_mesh(n_devices=...)`, and then the same
pipeline through the same public API.  One process drives every chip; the
window operator becomes one SPMD operator whose keyed exchange is an
`all_to_all` inside its update step."""

from __future__ import annotations

from jobs import keyed_window

output_fields = keyed_window.output_fields


def build(env, source, sink, config: dict) -> None:
    env.set_mesh(n_devices=config["mesh_chips"]["here"])
    keyed_window.build(env, source, sink, config)
