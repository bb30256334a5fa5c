"""Print the optimised HLO of a benchmark cell's step, compiled on this
box for a described v5e, without source locations — to ask whether an
edit changed the program the chip runs before spending chip time on it.

    JAX_PLATFORMS=cpu python3 tools/step_hlo.py gpt2m_4chip > new.txt
    # the same in a `git archive` of the other commit (copy this file
    # there if it predates it), then: cmp old.txt new.txt

``op_name`` paths, collectives with their replica groups and
``input_output_alias`` stay. A Mosaic call's payload is MLIR bytecode with
locations inside: it is replaced by the digest of its printed form without
them. Nothing runs: equal text is equal programs, not equal times.

``--strip-metadata`` drops every instruction's ``metadata={...}`` too, so
that an edit which only renames or adds scopes (docs/tracing.md, "Scopes in
a compiled step") gives equal text: the names changed and nothing else did.
"""

import base64
import hashlib
import os
import re
import sys

_LOCATION_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*", re.M)
_METADATA = re.compile(r',? metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')


def without_source_locations(hlo_text: str) -> str:
    """A compiled program's text less what records *where* it was traced
    from — the header's file, function, location and stack-frame tables
    and each instruction's ``stack_frame_id`` — which differs even between
    two traces of one program in one process."""
    return re.sub(r" stack_frame_id=\d+", "",
                  _LOCATION_TABLES.sub("", hlo_text))


def without_metadata(hlo_text: str) -> str:
    """The text less each instruction's ``metadata={...}``: ``op_name`` (the
    scopes it was traced under), ``op_type`` and the source position."""
    return _METADATA.sub("", hlo_text)


def _payload_digest(match) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True   # serialised as stable_mosaic
    with context:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        printed = module.operation.get_asm(enable_debug_info=False)
    return '"body":"sha256:%s"' % hashlib.sha256(printed.encode()).hexdigest()


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from chipbench import aot, cell as cells

    # written for a described chip, a cache entry cannot be read back here
    jax.config.update("jax_enable_compilation_cache", False)
    topology = topologies.get_topology_desc(platform="tpu",
                                            topology_name=aot.TOPOLOGY)
    cell = next(a for a in sys.argv[1:] if not a.startswith("--"))
    text = without_source_locations(aot.compile_cell(
        cells.Spec().cell(cell), topology.devices).as_text())
    if "--strip-metadata" in sys.argv[1:]:
        text = without_metadata(text)
    sys.stdout.write(re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _payload_digest,
                            text))


if __name__ == "__main__":
    main()
