# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (e.g. table1,fig6a)")
    args = ap.parse_args()
    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    from . import paper_tables
    subset = args.only.split(",") if args.only else list(paper_tables.ALL)
    print("name,us_per_call,derived")
    failed = []
    for name in subset:
        fn = paper_tables.ALL[name]
        try:
            for row in fn():
                print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
    if failed:
        sys.exit(f"benchmarks failed: {failed}")


if __name__ == '__main__':
    main()
