# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):   # `python benchmarks/run.py`
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    from repro.core.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks.paper_benches import ALL
    print("name,us_per_call,derived")
    failures = 0
    for bench in ALL:
        try:
            for name, us, derived in bench():
                print(f"{name},{us:.1f},{derived}")
                sys.stdout.flush()
        except Exception as e:  # keep the harness going; report the failure
            failures += 1
            print(f"{bench.__name__}_ERROR,0.0,{type(e).__name__}:{e}")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
