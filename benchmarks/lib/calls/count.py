"""The call Count(<tree>): node ("count", tree). Its answer is an int, as
in the server's JSON, so the two compare with `==`.

In a mix: {"call": "Count", "tree": <tree>}.
"""

from lib import query


def build(spec: dict, tree) -> tuple:
    return ("count", tree(spec["tree"]))


def to_pql(node: tuple) -> str:
    return f"Count({query.tree_pql(node[1])})"


def answer(ref, node: tuple) -> int:
    return ref.count(ref.eval(node[1]), None)


def same(got, want: int) -> bool:
    return isinstance(got, int) and not isinstance(got, bool) and got == want


def bytes_needed(work, node: tuple) -> int:
    return sum(work.row_bytes(f, r) for f, r in query.leaves(node[1]))
