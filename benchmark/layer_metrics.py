"""Per-layer metrics, each a small file of its own under layer_metrics/.

`layer_metrics/<name>.json` holds the metric's layer, unit, the end-to-end
metric it should move, its source, and how it is read, one of:

  "expr":    arithmetic over the worker's `JaxEngine.stats()`:
             d("key")      the counter's difference over the window
             dsum("glob")  the summed differences of every matching counter
             end("a.0.b")  a value after the window (dots walk dicts, lists)
  "reducer": the name of a file under reducers/, whose `read(ctx)` takes the
             metric from the client's counts, the stats or the reduced trace

A reader that finds nothing to read (no trace, a missing counter, a zero
denominator) returns nothing, and the harness leaves the metric out.
"""

from __future__ import annotations

import ast
import fnmatch
import importlib.util
import json
import operator
import os
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
OPS = {ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
       ast.Div: operator.truediv}


class Nothing(Exception):
    """The metric has nothing to read in this run."""


def walk(tree, dotted: str):
    for part in dotted.split("."):
        try:
            tree = tree[int(part)] if isinstance(tree, list) else tree[part]
        except (KeyError, IndexError, ValueError):
            raise Nothing(dotted)
    if tree is None:
        raise Nothing(dotted)
    return tree


def evaluate(expr: str, stats0: dict, stats1: dict, stats2: dict) -> float:
    def d(key):
        return walk(stats1, key) - walk(stats0, key)

    def dsum(glob):
        keys = [k for k in stats1 if fnmatch.fnmatchcase(k, glob)]
        if not keys:
            raise Nothing(glob)
        return sum(stats1[k] - stats0.get(k, 0) for k in keys)

    calls = {"d": d, "dsum": dsum, "end": lambda key: walk(stats2, key)}

    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
            return node.value
        if isinstance(node, ast.BinOp) and type(node.op) in OPS:
            return OPS[type(node.op)](ev(node.left), ev(node.right))
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in calls and len(node.args) == 1
                and isinstance(node.args[0], ast.Constant)):
            return calls[node.func.id](node.args[0].value)
        raise ValueError(f"not allowed in a metric's expression: {ast.dump(node)}")

    return ev(ast.parse(expr, mode="eval"))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def read(name: str, ctx: dict) -> Optional[float]:
    spec = load(name)
    try:
        if "expr" in spec:
            return float(evaluate(spec["expr"], ctx["stats0"], ctx["stats1"], ctx["stats2"]))
        path = os.path.join(HERE, "reducers", f"{spec['reducer']}.py")
        module_spec = importlib.util.spec_from_file_location(spec["reducer"], path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        value = module.read(ctx)
        return None if value is None else float(value)
    except (Nothing, ZeroDivisionError):
        return None


def read_all(metrics: list, ctx: dict) -> dict:
    return {m["name"]: read(m["name"], ctx) for m in metrics}
