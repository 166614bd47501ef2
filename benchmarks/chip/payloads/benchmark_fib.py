# upstream examples/benchmark-fib.py: ITERS iterations of iterative fib(N),
# pure CPython big-integer arithmetic; the dispatch shim must stay off this
# path. What differs from upstream is listed in configs/toolcalls-1chip.json:
# no wall clock printed, since stdout is compared.


def fib(n: int) -> int:
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


for _ in range(P["ITERS"]):
    result = fib(P["N"])

print(f"fib({P['N']}) x{P['ITERS']} = {str(result)[:10]}...")
