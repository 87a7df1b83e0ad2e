"""Names of the device work of every step program, in one place.

Device code opens these with ``jax.named_scope``: the name lands in each
lowered op's ``op_name`` metadata (``jit(decode_step)/while/body/attention/
dot_general``; the backward pass wraps it as ``transpose(jvp(attention))``),
which the profiler reports per device op, so a trace splits a program's
device time by layer. Scopes change metadata only, never the compiled
program (``tests/test_scopes.py``).

Every ``pl.pallas_call`` carries one of the kernel names below, so a kernel
keeps its name in a trace whatever program calls it.
"""

from __future__ import annotations

import functools

import jax

# device scopes
ATTENTION = "attention"      # core/attention.py entry points, every impl
KV_WRITE = "kv_write"        # K/V rows scattered into the page pool
MLP = "mlp"                  # dense MLP or MoE feed-forward of a block
LOGITS = "logits"            # final norm and unembedding
LOSS = "loss"                # cross-entropy over the logits
OPTIMIZER = "optimizer"      # gradient clip and optimizer update
SAMPLE = "sample"            # token sampling from logits

DEVICE_SCOPES = (ATTENTION, KV_WRITE, MLP, LOGITS, LOSS, OPTIMIZER, SAMPLE)

# Pallas kernels
FLASH_FWD = "flash_fwd"
FLASH_DQ = "flash_dq"
FLASH_DKV = "flash_dkv"
FLASH_PREFILL_PAGED_FWD = "flash_prefill_paged_fwd"
FLASH_PREFILL_PAGED_DQ = "flash_prefill_paged_dq"
FLASH_PREFILL_PAGED_DKV = "flash_prefill_paged_dkv"
FLASH_DECODE = "flash_decode"
FLASH_DECODE_PAGED = "flash_decode_paged"


def named(name: str):
    """Context manager naming the device work traced inside it."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: trace the function's device work under scope ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with named(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
