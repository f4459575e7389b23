"""A model family that exists only for the tests: each function returns a
fixed, recognisable value, so a test sees which family's code the harness
ran.  Found by a configuration's ``arch.family == "stub"`` once the tests
add this directory to ``spec.FAMILY_DIRS``."""

import jax.numpy as jnp

FLOPS = 1.0e9
GAP = 0.25


def make(arch, seed, mesh):
    return {"seed": seed, "chips": mesh.size}


def to_program(w, arch):
    return w


def padded_len(n):
    return n


def gaps(w, arch, tokens, targets):
    return jnp.where(targets >= 0, GAP, 0.0)


def projections(arch):
    return [("only", 1, 1)]


def attended(arch, pos):
    return 1


def prefill_flops(arch, n):
    return FLOPS * n


def decode_flops(arch, positions):
    return FLOPS * len(positions)


def axq_call_least_s(arch, M, peaks, unembed):
    return 1.0e-3


def decode_attn_least_s(arch, positions, peaks, kv_dtype):
    return 1.0e-3
