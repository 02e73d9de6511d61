"""Fit the document lengths of a traffic file to a public code corpus.

    python3 bench/tools/fit_doc_lengths.py <Lib directory of CPython>

One document is one Python function or method, as CodeSearchNet
(arXiv:1909.09436) cuts code into functions: every ``def`` and ``async
def``, nested ones too, from its first decorator to its last line, in
bytes. Directories named ``test``, ``tests`` or ``idle_test`` and files
generated by the build (``_sysconfigdata*``, ``config-*``) are left out.
Lengths in tokens are bytes over ``--bytes-per-token``: StarCoderData, the
corpus the StarCoder tokenizer was made for, holds 783 GB of code in
about 250 B tokens (its dataset card), 3.13 bytes a token.

Prints one JSON object: the number of functions, the median and the
quartiles in bytes, and the fitted lognormal's median in tokens and its
sigma, and the share under 16 tokens.
"""
from __future__ import annotations

import argparse
import ast
import json
import os
import sys
from typing import Iterator, List

import numpy as np

SKIP_DIRS = {"test", "tests", "idle_test", "site-packages"}


def sources(root: str) -> Iterator[str]:
    for d, dirs, files in os.walk(root):
        dirs[:] = sorted(x for x in dirs
                         if x not in SKIP_DIRS and not x.startswith("config-"))
        for f in sorted(files):
            if f.endswith(".py") and not f.startswith("_sysconfigdata"):
                yield os.path.join(d, f)


def function_bytes(src: bytes) -> List[int]:
    """Length in bytes of every function in ``src``, whole lines."""
    tree = ast.parse(src)
    starts = np.cumsum([0] + [len(line) + 1 for line in src.split(b"\n")])
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            first = min([node.lineno] + [d.lineno for d in
                                          node.decorator_list])
            out.append(int(starts[node.end_lineno] - starts[first - 1]))
    return out


def fit(lengths: List[int], bytes_per_token: float) -> dict:
    """The lognormal by maximum likelihood: mu and sigma are the mean and
    the standard deviation of log length; its median is exp(mu)."""
    x = np.asarray(lengths, dtype=np.float64)
    q1, med, q3 = np.percentile(x, [25, 50, 75])
    return {"functions": len(x), "median_bytes": float(med),
            "quartiles_bytes": [float(q1), float(q3)],
            "median_tokens": float(np.exp(np.log(x).mean())
                                   / bytes_per_token),
            "sigma": float(np.log(x).std()),
            "share_under_16_tokens": float(np.mean(x < 16 * bytes_per_token))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("lib")
    ap.add_argument("--bytes-per-token", type=float, default=783 / 250)
    args = ap.parse_args(argv)
    lengths: List[int] = []
    for path in sources(args.lib):
        with open(path, "rb") as f:
            try:
                lengths.extend(function_bytes(f.read()))
            except SyntaxError:
                print(f"skipped, not Python 3: {path}", file=sys.stderr)
    print(json.dumps(fit(lengths, args.bytes_per_token)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
