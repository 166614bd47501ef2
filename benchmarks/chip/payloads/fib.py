# upstream examples/fib.py: the minimal non-array workload; the dispatch
# shim must stay entirely off this path.


def fib(n: int) -> int:
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


print(fib(P["N"]))
