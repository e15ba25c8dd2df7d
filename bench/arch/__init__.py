"""Architectures: what the harness knows of a model family, one module
per published ``model_type``.

A configuration file names its architecture by the published key
``model.model_type``, and :func:`of` imports ``bench.arch.<model_type>``.
So a new family comes as new files: its module here and its
configuration under ``configs/``.  The generic harness (``run``,
``batch``, ``serve``, ``readings``, ``rehearse``, ``sweep`` and the
metric readers) reaches a family only through :func:`of`.

A module gives:

``sizes(cfg)``
    The configuration's sizes: a hashable object, which jitted functions
    take as a static argument.  Generic code reads only ``vocab``,
    ``layers``, ``d`` and ``fe`` (the routed expert's width) from it:
    every family's routed expert is ``d`` wide with a gated hidden layer
    of ``fe`` (``bench.work.expert_ffn``).  The rest is the family's.
``reduced(cfg)``
    The same configuration at a size a CPU test holds (tests only).
``step_config(cfg)``
    ``(StepConfig, placement)``: the program's launch-step configuration
    for the configuration's deployment, and the placement of replica
    slots that the weights and the routing tables follow.  The
    ``StepConfig``'s ``cfg`` is the program's ``ModelConfig`` and its
    ``dist`` what this chip computes of the EP group; the engine takes
    both from it as well.
``program_params(cfg, seed, placement)``
    The program's parameter tree, drawn on the device from the seed in
    the served dtype, holding the slots that the ``dist`` computes.
``served_gaps(s, seed, seqs, pad_to, *, control=False)``
    The plain float32 reference that decides ``correct``, and its
    control: per sequence, the gaps of the served tokens below the
    reference's best logit (see ``bench.reference``, the Qwen family's).
``pad_len(n)``
    The length the reference computes a sequence of ``n`` tokens at.
``logits(s, seed, tokens, *, control=False)``
    The reference's logits of one sequence (tests).
``step_flops(s, prefill, decode_positions)``, ``flash_decode(s, positions)``
    The family's work counts: model FLOPs of a step, and one layer's
    decode attention as (FLOPs, bytes).

No generic code assumes that one chip computes the whole EP group: a
family whose expert layer holds one EP rank's slots builds that
``dist`` in ``step_config``, draws only those slots and gives its
reference the same share.
"""
from __future__ import annotations

import importlib
import pkgutil


def known() -> list[str]:
    """The architecture modules there are."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__)
                  if not m.name.startswith("_"))


def of(cfg: dict):
    """The architecture module that the configuration's ``model_type``
    names; an unknown one is an error, never a default."""
    kind = cfg["model"].get("model_type")
    name = f"{__name__}.{kind}"
    if isinstance(kind, str) and kind.isidentifier() \
            and not kind.startswith("_"):
        try:
            return importlib.import_module(name)
        except ModuleNotFoundError as e:
            if e.name != name:
                raise
    raise SystemExit(f"no architecture module for model_type {kind!r}; "
                     f"bench/arch has {known()}")
