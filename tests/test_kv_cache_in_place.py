"""The decode cache lives in the layer scan's carry and is written in place.

Each decode step writes one slot of each layer's ring into the stacked
cache ``(n_groups, B, kv_len, KV, hd)`` and attends over that layer read
from the stack.  The stack is never an xs or ys of the layer scan, so no
step slices a layer's ring out, restacks it into a fresh buffer, or copies
the whole stack into the decode loop's carry.  Checked on the traced
program (jaxpr), on the compiled CPU program (HLO text) and through the
``kv_writes`` trace counter.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tf
from repro.serve import steps
from repro.train.steps import init_train_state

B, S, MAX_LEN, N_STEPS = 2, 8, 24, 6
ARCHS = ("deepseek_7b", "starcoder2_7b")      # full-length MHA ring; GQA window


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in ARCHS:
        cfg = get_config(arch).reduced()
        out[arch] = (cfg, init_train_state(cfg, jax.random.PRNGKey(0)).params)
    return out


def _cache(cfg, params, quantized=False):
    return tf.init_cache(cfg, B, MAX_LEN, dtype=params["embed"].dtype,
                         quantized=quantized)


def _stacks(cache):
    """Shapes and dtypes of the stacked attention cache leaves."""
    return {(leaf.shape, leaf.dtype)
            for key, blk in cache["groups"].items() if key.endswith("attn")
            for leaf in jax.tree.leaves(blk)}


def _generate_args(cfg, params):
    prompts = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab,
                          jnp.int32)
    return params, prompts, None, jax.random.PRNGKey(0), jnp.float32(0.0)


def _decode_args(cfg, params, quantized):
    return (params, jnp.zeros((B, 1), jnp.int32),
            _cache(cfg, params, quantized), jnp.int32(S + 1), None)


# --------------------------------------------------------------------------- #
# traced program: the stack rides in the carry, never in xs / ys
# --------------------------------------------------------------------------- #
def _sub_jaxprs(v):
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr") and hasattr(v, "consts"):
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _scans(sub)


def _scan_leaves(closed):
    """(carry, xs, ys) avals ``(shape, dtype)`` of every scan in the trace."""
    carry, xs, ys = set(), set(), set()
    for eqn in _scans(closed.jaxpr):
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        av = lambda vs: {(v.aval.shape, v.aval.dtype) for v in vs}
        carry |= av(eqn.invars[nc:nc + nk])
        xs |= av(eqn.invars[nc + nk:])
        ys |= av(eqn.outvars[nk:])
    return carry, xs, ys


def _assert_carried(closed, stacks):
    carry, xs, ys = _scan_leaves(closed)
    slabs = {(shape[1:], dtype) for shape, dtype in stacks}
    assert stacks <= carry                       # the mechanism is engaged
    for shape, dtype in xs | ys:
        assert (shape, dtype) not in stacks, (shape, dtype)
        assert (shape[1:], dtype) not in slabs, (shape, dtype)


@pytest.mark.parametrize("arch", ARCHS)
def test_generate_layer_scan_carries_the_cache(models, arch):
    cfg, params = models[arch]
    gen = steps.make_generate_fn(cfg, MAX_LEN, N_STEPS)
    closed = jax.make_jaxpr(gen)(*_generate_args(cfg, params))
    _assert_carried(closed, _stacks(_cache(cfg, params)))


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_decode_step_layer_scan_carries_the_cache(models, quantized):
    """The eager per-token step too, with the float and the int8 (HC3)
    cache, whose ``int8_q`` / ``int8_s`` leaves are each carried."""
    cfg, params = models["deepseek_7b"]
    args = _decode_args(cfg, params, quantized)
    closed = jax.make_jaxpr(steps.make_decode_fn(cfg))(*args)
    stacks = _stacks(args[2])
    assert len(stacks) == (2 if quantized else 1)
    _assert_carried(closed, stacks)


# --------------------------------------------------------------------------- #
# compiled program: no copy or fresh buffer of the stack in the decode loop
# --------------------------------------------------------------------------- #
_COMP = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_INST = re.compile(r"^\s*(ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$")
_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "int8": "s8"}


def _parse_hlo(text):
    """{computation: [(is_root, shape, opcode, rest)]} and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _INST.match(line)):
            shape = re.sub(r"\{[^}]*\}$", "", m.group(3))   # drop layout
            comps[cur].append((bool(m.group(1)), shape, m.group(4),
                               m.group(5)))
    return comps, entry


def _reachable(comps, start):
    """Computations run from ``start``, through loops, calls and branches
    (fused computations are part of their fusion instruction)."""
    seen, todo = set(), [start]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for _, _, op, rest in comps[c]:
            if op != "fusion":
                todo += re.findall(
                    r"(?:body|condition|to_apply|branch_computations|calls)"
                    r"=\{?%([\w.\-]+)", rest)
                todo += re.findall(r"%([\w.\-]+)", "".join(re.findall(
                    r"branch_computations=\{([^}]*)\}", rest)))
    return seen


def _body(rest):
    return re.search(r"body=%([\w.\-]+)", rest).group(1)


def _decode_loop(comps, entry):
    """The computations of the decode loop: the while whose body runs
    another (each step's layer scan)."""
    for c in _reachable(comps, entry):
        for _, _, op, rest in comps[c]:
            if op != "while":
                continue
            inner = _reachable(comps, _body(rest))
            if any(o == "while" for cc in inner for _, _, o, _ in comps[cc]):
                return inner
    raise AssertionError("no decode loop in the program")


def _moves(comps, where, stacks):
    """Copies and broadcasts (alone or as a fusion's root) whose result has
    a stacked cache leaf's shape."""
    names = {_HLO_DTYPE[jnp.dtype(d).name] + str(list(s)).replace(" ", "")
             for s, d in stacks}
    roots = {c: op for c, insts in comps.items()
             for root, _, op, _ in insts if root}
    hits = []
    for c in where:
        for _, shape, op, rest in comps[c]:
            if shape not in names:
                continue
            if op == "fusion":
                called = re.search(r"calls=%([\w.\-]+)", rest).group(1)
                op = roots.get(called)
            if op in ("copy", "broadcast"):
                hits.append((c, shape, op))
    return hits


@pytest.mark.parametrize("arch", ARCHS)
def test_generate_decode_loop_moves_no_cache(models, arch):
    """The parent program copied the whole K and V stacks into the decode
    loop's carry and allocated a fresh stack (the scan's ys) every step."""
    cfg, params = models[arch]
    gen = jax.jit(steps.make_generate_fn(cfg, MAX_LEN, N_STEPS))
    text = gen.lower(*_generate_args(cfg, params)).compile().as_text()
    comps, entry = _parse_hlo(text)
    stacks = _stacks(_cache(cfg, params))
    assert _moves(comps, _decode_loop(comps, entry), stacks) == []
    # the pattern does find the prefill's one zero-filled allocation
    assert _moves(comps, comps, stacks)


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_decode_step_moves_no_cache(models, quantized):
    """The eager step, donated as the engines jit it: no copy of the stack
    and no fresh stack anywhere in the program."""
    cfg, params = models["deepseek_7b"]
    args = _decode_args(cfg, params, quantized)
    dec = jax.jit(steps.make_decode_fn(cfg), donate_argnums=(2,))
    comps, _ = _parse_hlo(dec.lower(*args).compile().as_text())
    assert _moves(comps, comps, _stacks(args[2])) == []


# --------------------------------------------------------------------------- #
# the kv_writes counter
# --------------------------------------------------------------------------- #
def _attn_blocks(cfg):
    return sum(kind == "attn" for kind in cfg.block_pattern)


@pytest.mark.parametrize("arch", ARCHS + ("recurrentgemma_2b",))
def test_kv_writes_ticks_once_per_attention_block(models, arch):
    """A decode trace ticks ``token`` and a prefill trace ``slab``, once per
    attention block traced: one per block of the scanned pattern."""
    if arch in models:
        cfg, params = models[arch]
    else:                                        # ("rec", "rec", "attn")
        cfg = get_config(arch).reduced()
        params = init_train_state(cfg, jax.random.PRNGKey(0)).params
    n = _attn_blocks(cfg)
    tf.KV_WRITES.clear()
    jax.make_jaxpr(steps.make_decode_fn(cfg))(
        *_decode_args(cfg, params, False))
    assert dict(tf.KV_WRITES) == {"token": n}
    tf.KV_WRITES.clear()
    jax.make_jaxpr(steps.make_prefill_fn(cfg, MAX_LEN))(
        *_generate_args(cfg, params)[:3])
    assert dict(tf.KV_WRITES) == {"slab": n}
    tf.KV_WRITES.clear()
    jax.make_jaxpr(steps.make_generate_fn(cfg, MAX_LEN, N_STEPS))(
        *_generate_args(cfg, params))
    assert dict(tf.KV_WRITES) == {"slab": n, "token": n}
