# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import json


def main() -> None:
    from benchmarks import preprocessing, reward_curves, roofline, \
        scaling, sde_dynamics, serving

    suites = [
        ("sde_dynamics (paper Table 1)", sde_dynamics.run),
        ("reward_curves (paper Fig 2)", reward_curves.run),
        ("preprocessing (paper Table 2)", preprocessing.run),
        ("roofline (deliverable g)", roofline.run),
        ("scaling (repro.distributed mesh layouts)", scaling.run),
        ("serving (repro.serving bucketed engine)", serving.run),
    ]
    print("name,us_per_call,derived")
    # a failing suite raises: no later row can pass for a complete run
    for _, fn in suites:
        for row in fn():
            print(f"{row['name']},{row['us_per_call']},"
                  f"\"{json.dumps(row['derived'])}\"")


if __name__ == "__main__":
    main()
