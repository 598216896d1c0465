"""Model families: everything the harness knows about a model's block lives
in one module of this package, and a configuration file picks its module
by the key ``block`` (``"dense"`` where the key is absent).

A family is added as files alone: ``bench/harness/arch/<family>.py``, its
``bench/configs/<name>.json`` with ``"block": "<family>"``, the cells in
``BENCHMARK.json`` and any new ``bench/metrics/*.py`` readers.  The shared
code (``cell``, ``serve``, ``check``, ``record``, ``run.py``,
``control.py``, ``bench/kernels`` and ``bench/metrics``) calls nothing of
a model but the names below, which a family module must define
(``harness.arch.dense`` is the model to copy):

Shapes
    ``Dims``: a frozen (hashable) dataclass with at least ``layers`` and
    ``vocab``.  ``dims_of(config) -> Dims``.

The width check
    ``program_config(config)``: the program's ArchConfig for the file, with
    the file's depth and RoPE base; raises ``ValueError`` where a width of
    the file and of the program differ, or where the program's block is not
    this family's.

Weights (data made from the seed, ``reference.root_key``)
    ``make_weights(seed, dims) -> dict`` of float32 arrays, made on the
    device in one jitted call and the same for the same seed;
    ``program_params(weights)``: the same arrays in the program's parameter
    tree.  ``serve.build`` refuses a tree whose shapes or dtypes differ from
    the program's own ``init``.

The plain reference (it imports nothing of the program)
    ``stated(config)`` and ``control(config)``: hashable precisions, the one
    the file states and the one a step below (``reference.Precision`` with
    ``reference.stated``/``control`` where the family has no other).
    ``forward(weights, tokens, n_prompt, *, d, p) -> (logits, rows)``: the
    teacher-forced pass over one request padded to a fixed length, rows
    before ``n_prompt`` as the prefill computes them and the rest as decode
    steps do; ``logits`` (S, vocab) float32, ``rows`` the cache rows of
    every layer as the pages store them, (L, parts, S, groups, width).

Cache rows
    ``served_rows(engine, pages, n_pos, config)``: the first ``n_pos``
    rows of one request read back from the engine's page pool, decoded to
    float32 on the host, in ``forward``'s layout (L, parts, n_pos, groups,
    width).  Dense: parts K and V, one group per KV head of ``head_dim``.

Counts, from the recorded ``record.Run`` (its spans, and any counters the
family's own readers keep), so that rows that depend on routing can be
counted
    ``matmul_calls(run)``: ``(M, K, N, repeats)`` of every residue matmul
    of the run's traced interval (``bench/kernels/rns_matmul.py``);
    ``paged_decode(run)``: ``(operations per cached position, bytes of
    the query and output rows, layers)`` of one slot's paged decode step
    (``bench/kernels/flash_paged_decode.py``);
    ``prompt_flops(run, n)`` and ``token_flops(run, n)``: the useful model
    FLOPs of a prompt of ``n`` tokens and of a generated token whose step
    attends over ``n`` positions (``bench/kernels/model_step.py``, read by
    ``mfu``).

What the shared check does with it (``harness/check.py``): after the
window it reads each sampled request's cache rows with ``served_rows``,
remakes the weights with ``make_weights``, runs ``forward`` at ``stated``
over the prompt and the served tokens, and compares the gap of each served
token's logit below the reference's best (``tok_gap*``) and the relative
error of each row of ``width`` (``kv_err*``: its median per layer, part
and writer, the worst of those; ``kv_err_layer0`` and ``kv_err_layer1``
for one layer).  The control puts ``forward`` at ``control`` in the
program's place.  The limits are the configuration file's ``check``.
"""
from __future__ import annotations

import importlib
import pkgutil

KEY = "block"
DEFAULT = "dense"
INTERFACE = ("Dims", "dims_of", "program_config", "make_weights",
             "program_params", "stated", "control", "forward",
             "served_rows", "matmul_calls", "paged_decode", "prompt_flops",
             "token_flops")


def _missing(module) -> list[str]:
    return [n for n in INTERFACE if not hasattr(module, n)]


def families() -> list[str]:
    """The modules of this package that define the whole interface."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__)
                  if not _missing(importlib.import_module(
                      f"{__name__}.{m.name}")))


def family_of(config: dict):
    """The family module that ``config`` names under ``block``; raises
    ``ValueError`` naming the key and the families there are where it
    names none."""
    name = config.get(KEY, DEFAULT)
    module = None
    if isinstance(name, str) and name.isidentifier():
        try:
            module = importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"{__name__}.{name}":
                raise
    if module is None or _missing(module):
        why = ("no module" if module is None else
               f"a module without {', '.join(_missing(module))}")
        raise ValueError(
            f"configuration key {KEY!r} names {name!r}, which has {why} "
            f"under bench/harness/arch; families: {', '.join(families())}")
    return module
