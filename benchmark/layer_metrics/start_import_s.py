"""The package's lazy imports (span ``dls.start/import``, opened around each
first import of a public name: the modules that pull in jax, flax, optax and
orbax): ``import_s`` of the program's ``startup`` record. A caller that
imported jax first, as the harness does, has paid that part itself, and so
has one that imports the package's submodules by their own names
(``from <package>.session import Session``): that import passes by the span
and its time is in ``caller_s``. ``joyai_llm_flash.fit_s16k`` is such a
caller (the cell's own files import the models ahead of ``Session``), so the cell is not among this metric's ``workloads``."""

from benchmark.harness import startup


def read(ctx):
    return startup.seconds(ctx, "import_s")
