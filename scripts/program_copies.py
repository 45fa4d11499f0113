#!/usr/bin/env python3
"""The copies of its PARAMETERS that a compiled serving program holds
(ISSUE 63): compile a benchmark configuration's programs at their real sizes
for a DESCRIBED v5e — no chip attached, seconds a program — and print every
``copy`` / ``copy-start`` of the optimised HLO whose operand is a parameter
of the program (through async pairs, bitcasts, a ``ConcatBitcast`` of the
parameter's slices, the state of a ``while`` and a fusion's own parameters):
its shape, layout and memory space, the parameter and ITS layout, whether the
copy re-lays what it reads (another order of dimensions or another tiling
than its operand's — the parameter as it lies, or a bitcast that reads a
square one as its transpose; a prefetch into VMEM in the parameter's own
layout re-lays nothing) and whether it sits inside or outside a loop.

    python3 scripts/program_copies.py evabyte-6.5b-l16 ouro-2.6b
    python3 scripts/program_copies.py mistral-7b-v0.3-tp4 --programs decode
    python3 scripts/program_copies.py mixtral-8x7b-l4 --layers 2 --verbose

A re-laid copy OUTSIDE the loop is paid once a call of the program, whatever
K is (a weight the compiler wanted transposed: layout assignment inserts the
copy and hoists it as loop-invariant; into HBM it is read and written, into
VMEM — memory space 1 — read); one INSIDE is paid every step. The last line
a program counts them, their bytes and the entry-level slices of parameters. ``relaid_copies`` is the reader, on the text alone:
``tests/test_chip_compile.py`` holds the decode programs to it.

A compile that passes is not a chip run: this says what a program moves, not
how long it takes. Run it under ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` where
another process holds libtpu.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ARRAY = re.compile(r"(\w+)\[([\d,]*)\](?:\{([\d,]*)(?::([^}]*))?\})?")
_NAME = re.compile(r"%([\w.\-]+)")
# what hands an array on as it lies: the root of a copy's operand is looked
# for through these
_PASSES = {"copy", "copy-start", "copy-done", "slice-start", "slice-done",
           "slice", "bitcast"}


@dataclass(frozen=True)
class Instr:
    name: str
    shape: str           # the result's type as printed (a tuple's: whole)
    op: str
    operands: tuple      # names, in order
    args: str            # the operands as printed (``parameter``'s: its k)
    attrs: str           # what follows the operands
    computation: str


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    return len(text)


def parse_hlo(text: str) -> tuple[dict, str]:
    """``({computation: {name: Instr}}, entry computation's name)`` of an
    optimised HLO module's text."""
    computations: dict = {}
    entry, current = "", None
    for line in text.splitlines():
        if not line.startswith(" "):
            head = line.split(" ", 2)
            if line.startswith("ENTRY "):
                current = entry = head[1].lstrip("%")
            elif line.startswith("%"):
                current = head[0].lstrip("%")
            else:
                continue
            computations[current] = {}
            continue
        body = line.strip()
        if body.startswith("ROOT "):
            body = body[5:]
        if current is None or " = " not in body or not body.startswith("%"):
            continue
        name, rest = body.split(" = ", 1)
        end = _balanced(rest, 0) if rest.startswith("(") \
            else rest.index(" ")
        shape, rest = rest[:end], rest[end:].lstrip()
        m = re.match(r"([\w\-]+)\(", rest)
        if not m:
            continue
        close = _balanced(rest, m.end() - 1)
        computations[current][name.lstrip("%")] = Instr(
            name.lstrip("%"), shape, m.group(1),
            tuple(_NAME.findall(rest[m.end():close - 1])),
            rest[m.end():close - 1], rest[close:], current)
    return computations, entry


def layout_of(shape: str) -> tuple:
    """``(dtype, dims, order of dimensions, tiling)`` of a printed array
    type — of a tuple's first member (a ``copy-start``'s destination) —
    without its memory space: where an array lies is no layout."""
    m = _ARRAY.search(shape)
    if not m:
        return ("", "", "", "")
    tiles = re.sub(r"S\(\d+\)", "", m.group(4) or "")
    return (m.group(1), m.group(2), m.group(3) or "", tiles)


class Program:
    """An optimised HLO text, read for where its arrays come from."""

    def __init__(self, text: str):
        self.computations, self.entry = parse_hlo(text)
        # computation -> (the instruction that calls it, what it is there)
        self.callers: dict = {}
        self.loop_bodies: set = set()
        for instrs in self.computations.values():
            for ins in instrs.values():
                for key, called in re.findall(
                        r"(body|condition|calls|to_apply)=%?([\w.\-]+)",
                        ins.attrs):
                    self.callers[called] = ins
                    if key == "body":
                        self.loop_bodies.add(called)

    def in_loop(self, computation: str) -> bool:
        """Whether ``computation`` runs inside a ``while``."""
        seen = set()
        while computation and computation not in seen:
            if computation in self.loop_bodies:
                return True
            seen.add(computation)
            caller = self.callers.get(computation)
            computation = caller.computation if caller else ""
        return False

    def _instr(self, computation: str, name: str) -> Optional[Instr]:
        return self.computations.get(computation, {}).get(name)

    def root(self, ins: Optional[Instr], index: Optional[int] = None,
             depth: int = 0) -> Optional[Instr]:
        """The ENTRY parameter that ``ins`` (member ``index`` of it, where
        it is a tuple) hands on as it lies, or None where it is computed."""
        if ins is None or depth > 64:
            return None

        def up(name, idx=index, comp=ins.computation):
            return self.root(self._instr(comp, name), idx, depth + 1)
        if ins.op == "parameter":
            if ins.computation == self.entry:
                return ins if index is None else None
            caller = self.callers.get(ins.computation)
            if caller is None:
                return None
            # a loop's body takes the loop's one operand; a fusion's or a
            # call's parameter k is its caller's operand k
            k = 0 if caller.op == "while" else int(ins.args)
            if k >= len(caller.operands):
                return None
            return up(caller.operands[k], comp=caller.computation)
        if ins.op == "get-tuple-element":
            if index is not None:
                return None
            m = re.search(r"index=(\d+)", ins.attrs)
            return up(ins.operands[0], int(m.group(1))) if m else None
        if ins.op == "tuple":
            if index is None or index >= len(ins.operands):
                return None
            return up(ins.operands[index], None)
        if ins.op == "opt-barrier":     # a tuple in, the same tuple out
            return up(ins.operands[0])
        if index is not None:
            return None
        if ins.op in _PASSES:
            below = self._instr(ins.computation, ins.operands[0])
            if ins.op in ("copy", "copy-start") and below is not None \
                    and layout_of(below.shape) != layout_of(ins.shape):
                return None     # a re-laid copy is a temporary, no parameter
            return up(ins.operands[0])
        if ins.op == "custom-call" and "ConcatBitcast" in ins.attrs:
            roots = {id(r): r for r in (up(o) for o in ins.operands)}
            return next(iter(roots.values())) if len(roots) == 1 else None
        return None

    def parameter_copies(self) -> list:
        """A dict a ``copy`` / ``copy-start`` whose operand is a parameter
        of the program."""
        found = []
        for comp, instrs in self.computations.items():
            for ins in instrs.values():
                if ins.op not in ("copy", "copy-start"):
                    continue
                operand = self._instr(comp, ins.operands[0])
                source = self.root(operand)
                if source is None:
                    continue
                dtype, dims, order, tiles = layout_of(ins.shape)
                _, p_dims, p_order, p_tiles = layout_of(source.shape)
                # what the copy reads: the parameter as it lies, or a
                # bitcast that reads the stored bytes as another order of
                # dimensions (a square matrix as its transpose)
                _, o_dims, o_order, o_tiles = layout_of(operand.shape)
                found.append({
                    "copy": ins.name, "op": ins.op,
                    "shape": f"{dtype}[{dims}]", "layout": f"{order}:{tiles}",
                    "memory_space": _memory_space(ins.shape),
                    "parameter": _argument(source),
                    "parameter_shape": f"[{p_dims}]",
                    "parameter_layout": f"{p_order}:{p_tiles}",
                    "relaid": (order, tiles) != (o_order, o_tiles)
                    or dims.count(",") != o_dims.count(","),
                    "where": "loop" if self.in_loop(comp) else "entry",
                    "computation": comp})
        return found

    def entry_parameter_slices(self) -> int:
        """``slice-start`` instructions of the entry computation that read
        a parameter."""
        instrs = self.computations.get(self.entry, {})
        return sum(1 for ins in instrs.values() if ins.op == "slice-start"
                   and self.root(self._instr(self.entry, ins.operands[0]))
                   is not None)


def _memory_space(shape: str) -> int:
    """The ``S(n)`` of a printed array type's layout (of a tuple's first
    member): 0 is HBM, 1 the chip's VMEM."""
    m = _ARRAY.search(shape)
    space = re.search(r"S\((\d+)\)", (m.group(4) or "") if m else "")
    return int(space.group(1)) if space else 0


def _argument(parameter: Instr) -> str:
    """An entry parameter by the path of the argument it is (``params[
    'layers'][0]['wq']``: a partitioned program's parameters are named
    ``param.N`` and carry the path as metadata), else by its name."""
    m = re.search(r'op_name="([^"]+)"', parameter.attrs)
    return m.group(1).replace("\\'", "'") if m else parameter.name


def relaid_copies(text: str) -> list:
    """The copies of an optimised HLO text that write a parameter of the
    program out again in a layout other than its own."""
    return [c for c in Program(text).parameter_copies() if c["relaid"]]


def compiled_programs(configuration: str, devices, kinds=(),
                      n_layers: int = 0):
    """``(key, compiled text)`` of a benchmark configuration's serving
    programs (those whose key starts with one of ``kinds``) compiled for
    described ``devices`` at the engine's shapes; ``n_layers`` cuts the
    depth. The dispatchers take the kernels the chip runs."""
    from dataclasses import replace

    sys.path.insert(0, ROOT)
    from benchmark import manifest, serve
    import tpu9.ops.attention as attention_ops
    import tpu9.ops.grouped_ffn as grouped_ops
    import tpu9.ops.held_ffn as held_ops
    import tpu9.utils
    # the declared introspection hook (``lowering_jobs``), driven from
    # outside as graphcheck drives it
    from tpu9.serving.graphs import (  # tpu9: noqa[BND001] a builder's tool compiles the engine's own programs through lowering_jobs
        GraphFactory, abstract_state)
    from tpu9.serving.presets import abstract_params_for
    from tpu9.serving.shard import MeshPolicy
    from tpu9.serving.shard.plan import Topology, parse_topology
    for module in (attention_ops, grouped_ops, held_ops, tpu9.utils):
        module.on_tpu = lambda: True
    m = manifest.load()
    config = manifest.load_config(m, configuration)
    family = manifest.family(config)
    cfg = family.program_config(family.model_sizes(config))
    if n_layers:
        cfg = replace(cfg, n_layers=n_layers)
    ecfg = serve.engine_config(config["engine"])
    t = parse_topology(config["engine"]["topology"]) or Topology(1, 1)
    policy = MeshPolicy(t, devices=devices[:t.n_chips])
    graphs = GraphFactory(cfg, ecfg, policy, chunk=ecfg.prefill_chunk)
    st = abstract_state(cfg, ecfg, policy)
    for key, fn, args in graphs.lowering_jobs(
            abstract_params_for(cfg, False), st["kv_cache"], st["pool"],
            st["scratch"], st["mb"], [ecfg.prefill_chunk], (), st["rng"]):
        if kinds and (key if isinstance(key, str) else key[0]) not in kinds:
            continue
        try:
            yield key, fn.lower(*args).compile().as_text()
        except Exception as exc:    # noqa: BLE001 — the compiler's word
            yield key, exc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configurations", nargs="*",
                    help="names of benchmark/configs (default: all)")
    ap.add_argument("--programs", default="",
                    help="kinds of program, comma-separated (decode, chunk, "
                         "chunkgroup, ...); default: every program")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers")
    ap.add_argument("--verbose", action="store_true",
                    help="print the copies that re-lay nothing too")
    ap.add_argument("--hlo-dir", default="",
                    help="write each compiled text here")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from benchmark import manifest
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    kinds = tuple(k for k in args.programs.split(",") if k)
    names = args.configurations or [c["name"]
                                    for c in manifest.load()["configs"]]
    for name in names:
        for key, text in compiled_programs(name, devices, kinds,
                                           args.layers):
            label = key if isinstance(key, str) \
                else "".join(str(k) for k in key)
            if isinstance(text, Exception):
                print(json.dumps({
                    "configuration": name, "program": label, "refused":
                    f"{type(text).__name__}: {text}"[:400]}), flush=True)
                continue
            if args.hlo_dir:
                os.makedirs(args.hlo_dir, exist_ok=True)
                with open(os.path.join(args.hlo_dir,
                                       f"{name}.{label}.hlo.txt"), "w") as f:
                    f.write(text)
            program = Program(text)
            copies = program.parameter_copies()
            for c in copies:
                if c["relaid"] or args.verbose:
                    print(json.dumps({"configuration": name,
                                      "program": label, **c}), flush=True)
            relaid = [c for c in copies if c["relaid"]]
            nbytes = sum(_bytes(c["shape"]) for c in relaid)
            print(json.dumps({
                "configuration": name, "program": label,
                "relaid_copies": len(relaid),
                "relaid_at_entry": sum(c["where"] == "entry"
                                       for c in relaid),
                "relaid_in_loop": sum(c["where"] == "loop" for c in relaid),
                "relaid_gb": round(nbytes / 1e9, 3),
                "copies_in_own_layout": len(copies) - len(relaid),
                "entry_parameter_slices": program.entry_parameter_slices()}),
                flush=True)
    return 0


def _bytes(shape: str) -> int:
    dtype, dims, _, _ = layout_of(shape)
    digits = re.search(r"\d+", dtype)      # ``pred`` has none: a byte
    bits = int(digits.group()) if digits else 8
    n = 1
    for d in dims.split(","):
        n *= int(d) if d else 1
    return n * bits // 8


if __name__ == "__main__":
    sys.exit(main())
