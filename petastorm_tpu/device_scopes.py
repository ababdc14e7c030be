"""The names the train steps carry on the device: one ``jax.named_scope``
vocabulary for ``models/llama.py``, ``models/resnet.py`` and
``ops/eva_attn.py``, and the rule that reads a compiled instruction's
``op_name`` back into ``(scope, phase)``.

A leaf module: strings only, no JAX and nothing of the telemetry package,
so the models and a reader of somebody's profile can both import it. A
named scope writes ``op_name`` metadata and nothing else; the optimised
program is the same with and without it, so there is no switch.

Every instruction of a compiled step lies in exactly one innermost scope
(``docs/observability.md``, "Model step", has the table: scope, around
what, innermost parent). The phase is not a scope of ours: it is read off
the wrappers JAX itself writes into the name stack (:func:`classify`).
"""
from __future__ import annotations

import re

PREFIX = "petastorm_tpu."

# models/llama.py
EMBED = PREFIX + "embed"
BLOCK = PREFIX + "block"
ATTN_QKV = PREFIX + "attn_qkv"
MLA_LATENT = PREFIX + "mla_latent"
ATTN_FULL = PREFIX + "attn_full"
ATTN_WINDOW = PREFIX + "attn_window"
ATTN_EVA = PREFIX + "attn_eva"
ATTN_OUT = PREFIX + "attn_out"
FFN = PREFIX + "ffn"
MOE_ROUTE = PREFIX + "moe_route"
MOE_EXPERTS = PREFIX + "moe_experts"
MOE_ROWS_IN = PREFIX + "moe_rows_in"
MOE_ROWS_BACK = PREFIX + "moe_rows_back"
MOE_SHARED = PREFIX + "moe_shared"
LOSS_HEAD = PREFIX + "loss_head"
OPTIMIZER = PREFIX + "optimizer"
# ops/eva_attn.py
EVA_PREP = PREFIX + "eva_prep"
# models/resnet.py
STEM = PREFIX + "stem"
STAGES = tuple(PREFIX + f"stage{n}" for n in range(4))
HEAD = PREFIX + "head"

#: Every scope, without the prefix.
DEVICE_SCOPES = tuple(name[len(PREFIX):] for name in (
    EMBED, BLOCK, ATTN_QKV, MLA_LATENT, ATTN_FULL, ATTN_WINDOW, ATTN_EVA,
    EVA_PREP, ATTN_OUT, FFN, MOE_ROUTE, MOE_EXPERTS, MOE_ROWS_IN,
    MOE_ROWS_BACK, MOE_SHARED, LOSS_HEAD, OPTIMIZER, STEM, *STAGES, HEAD))

_SCOPE = re.compile(re.escape(PREFIX) + r"(\w+)")


def classify(op_name: str) -> tuple:
    """``(scope | None, phase)`` of an instruction's ``op_name``.

    The scope is the innermost ``petastorm_tpu.<name>`` segment (the name
    stack runs outside in, so the last one: ``eva_prep`` inside
    ``attn_eva``, ``mla_latent`` inside ``attn_qkv``); None where there is
    none or the name is not in :data:`DEVICE_SCOPES`.

    The phase is JAX's own wrapper in the path: ``rematted_computation``
    anywhere (what a ``jax.checkpoint`` runs again) is ``remat``; else
    ``transpose(`` (what ``grad`` transposed) is ``bwd``; else the scope
    ``optimizer`` is ``update``; else ``fwd``. These are the wrappers', not
    the mathematics': a ``custom_vjp`` forward rule that makes its
    gradients in the same pass (``llama._chunked_xent``) books them under
    ``fwd``, and a backward rule that runs its forward again
    (``llama._expert_rows_bwd``) books that under ``bwd``.
    """
    found = _SCOPE.findall(op_name)
    scope = found[-1] if found and found[-1] in DEVICE_SCOPES else None
    if "rematted_computation" in op_name:
        return scope, "remat"
    if "transpose(" in op_name:
        return scope, "bwd"
    return scope, "update" if scope == "optimizer" else "fwd"
