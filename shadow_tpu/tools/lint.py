"""shadowlint CLI — lint gate + compiled-program audits, JSON output.

    python -m shadow_tpu.tools.lint                 # lint the package
    python -m shadow_tpu.tools.lint path/to/file.py # lint specific files
    python -m shadow_tpu.tools.lint --update-baseline
    python -m shadow_tpu.tools.lint --hlo-audit all # + lowering audit
    python -m shadow_tpu.tools.lint --hlo-audit phold,tgen
    python -m shadow_tpu.tools.lint --donation-audit # alias verifier
    python -m shadow_tpu.tools.lint --mem-audit      # peak-live budgets
    python -m shadow_tpu.tools.lint --mem-audit --update-baseline
    python -m shadow_tpu.tools.lint --tpu-audit all  # readiness gate
    python -m shadow_tpu.tools.lint --tpu-audit all --update-baseline
    python -m shadow_tpu.tools.lint --diff old.json new.json

Exit status: 0 when there are no findings outside the checked-in
baseline (and, with --hlo-audit / --donation-audit / --mem-audit /
--tpu-audit, every audited config meets its contract); 1 otherwise.
Output is a
single JSON document on stdout — machine-readable for scripts and CI
— with human one-liners on stderr.

The baseline (shadow_tpu/analysis/lint_baseline.json) holds accepted
findings keyed by (rule | path | function | source line) so they
survive line drift; stale entries are reported (not fatal) so the
baseline shrinks as findings are fixed. `--mem-audit
--update-baseline` refreshes the peak-live budgets
(shadow_tpu/analysis/MEM_BUDGETS.json) the same way, and `--tpu-audit
--update-baseline` the TPU-readiness baseline
(shadow_tpu/analysis/TPU_READINESS.json). `--diff` compares two saved
JSON reports and prints the per-config drift of op budgets, alias
counts, memory estimates, and TPU-readiness numbers (tile waste,
layout churn, predicted events/s floors) — the
review artifact for an intentional budget bump. See
docs/10-Static-Analysis.md.
"""

from __future__ import annotations

import argparse
import json
import sys

from shadow_tpu.analysis import lint as L


def _diff_reports(old: dict, new: dict) -> list[str]:
    """Human-readable per-config drift between two saved reports."""
    lines: list[str] = []

    def _num(section: str, cfg: str, key: str, a, b) -> None:
        if a != b and isinstance(a, (int, float)) \
                and isinstance(b, (int, float)):
            d = b - a
            delta = f"{d:+d}" if isinstance(d, int) else f"{d:+.2f}"
            lines.append(f"{section} {cfg}: {key} {a} -> {b} ({delta})")

    oh, nh = old.get("hlo_audit", {}), new.get("hlo_audit", {})
    for cfg in sorted(set(oh) | set(nh)):
        oo = oh.get(cfg, {}).get("ops", {})
        no = nh.get(cfg, {}).get("ops", {})
        for op in sorted(set(oo) | set(no)):
            _num("ops", cfg, op, oo.get(op, 0), no.get(op, 0))

    od, nd = old.get("donation_audit", {}), new.get("donation_audit", {})
    for tgt in sorted(set(od) | set(nd)):
        for key in ("donated_leaves", "aliased_leaves"):
            _num("donation", tgt, key,
                 od.get(tgt, {}).get(key, 0), nd.get(tgt, {}).get(key, 0))

    om, nm = old.get("mem_audit", {}), new.get("mem_audit", {})
    for cfg in sorted(set(om) | set(nm)):
        oe = om.get(cfg, {}).get("estimate", {})
        ne = nm.get(cfg, {}).get("estimate", {})
        for key in ("args_bytes", "carry_bytes", "peak_bytes"):
            _num("memory", cfg, key, oe.get(key, 0), ne.get(key, 0))

    # tpu_readiness: waste / churn / VMEM / predicted-floor drift per
    # config, plus per-chip winner flips in the drain economics
    ot, nt = old.get("tpu_readiness", {}), new.get("tpu_readiness", {})
    for cfg in sorted((set(ot) | set(nt)) - {"drain_economics"}):
        oc, nc = ot.get(cfg, {}), nt.get(cfg, {})
        _num("tpu", cfg, "tile.waste_pct",
             oc.get("tile", {}).get("waste_pct", 0),
             nc.get("tile", {}).get("waste_pct", 0))
        _num("tpu", cfg, "tile.padded_bytes",
             oc.get("tile", {}).get("padded_bytes", 0),
             nc.get("tile", {}).get("padded_bytes", 0))
        och, nch = oc.get("churn", {}), nc.get("churn", {})
        for op in sorted(set(och) | set(nch)):
            for key in ("count", "hot"):
                _num("tpu", cfg, f"churn.{op}.{key}",
                     och.get(op, {}).get(key, 0),
                     nch.get(op, {}).get(key, 0))
        op_, np_ = oc.get("placement", {}), nc.get("placement", {})
        for op in sorted(set(op_) | set(np_)):
            _num("tpu", cfg, f"hot.{op}",
                 op_.get(op, {}).get("hot", 0),
                 np_.get(op, {}).get("hot", 0))
        of_, nf = oc.get("floors", {}), nc.get("floors", {})
        for cn in sorted(set(of_) | set(nf)):
            _num("tpu", cfg, f"floor.{cn}",
                 of_.get(cn, 0), nf.get(cn, 0))
    oe_, ne_ = (ot.get("drain_economics", {}),
                nt.get("drain_economics", {}))
    for model in sorted((set(oe_) | set(ne_))
                        - {"ok", "violations"}):
        ow = oe_.get(model, {}).get("winner", {})
        nw = ne_.get(model, {}).get("winner", {})
        for cn in sorted(set(ow) | set(nw)):
            a, b = ow.get(cn), nw.get(cn)
            if a != b:
                lines.append(
                    f"tpu drain_economics {model}: {cn} winner "
                    f"{a} -> {b}")
    return lines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m shadow_tpu.tools.lint",
        description="AST lint + HLO contract audit for shadow_tpu")
    ap.add_argument("paths", nargs="*",
                    help="files to lint (default: the shadow_tpu package)")
    ap.add_argument("--baseline", default=L.BASELINE_PATH,
                    help="baseline JSON path (default: packaged baseline)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding as new")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept current findings into the baseline and "
                         "exit 0")
    ap.add_argument("--hlo-audit", metavar="CONFIGS", default=None,
                    help="also lower + audit model configs: 'all' or a "
                         "comma list of phold,phold_net,tgen,tor,bitcoin")
    ap.add_argument("--donation-audit", action="store_true",
                    help="compile the production window-loop jits and "
                         "verify every donated leaf aliases; also runs "
                         "the harvest host-transfer census")
    ap.add_argument("--mem-audit", action="store_true",
                    help="estimate peak-live bytes per config and check "
                         "against MEM_BUDGETS.json")
    ap.add_argument("--tpu-audit", metavar="CONFIGS", default=None,
                    help="TPU-readiness audit (tile waste, layout "
                         "churn, hot-loop placement, roofline drain "
                         "economics) checked against "
                         "TPU_READINESS.json: 'all' or a comma list of "
                         "configs")
    ap.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"),
                    default=None,
                    help="compare two saved JSON reports and print the "
                         "contract drift (report-only; exit 0)")
    ap.add_argument("--output", default=None,
                    help="write the JSON report here instead of stdout")
    args = ap.parse_args(argv)

    if args.diff:
        with open(args.diff[0], "r", encoding="utf-8") as fh:
            old = json.load(fh)
        with open(args.diff[1], "r", encoding="utf-8") as fh:
            new_rep = json.load(fh)
        lines = _diff_reports(old, new_rep)
        for ln in lines:
            print(ln)
        if not lines:
            print("no contract drift")
        return 0

    findings = L.lint_paths(args.paths) if args.paths else L.lint_package()

    if args.update_baseline:
        entries = L.save_baseline(findings, args.baseline)
        print(f"baseline: {len(entries)} keys "
              f"({len(findings)} findings) -> {args.baseline}",
              file=sys.stderr)
        if args.mem_audit:
            from shadow_tpu.analysis import memory as M

            ests = {}
            for name in M.MEM_CONFIGS:
                try:
                    ests[name] = M.estimate_config(name)
                except RuntimeError as e:
                    print(f"mem baseline: {name} skipped ({e})",
                          file=sys.stderr)
            M.save_budgets(ests)
            print(f"mem baseline: {len(ests)} budgets -> "
                  f"{M.BUDGETS_PATH}", file=sys.stderr)
        if args.tpu_audit:
            from shadow_tpu.analysis import tpu_readiness as T

            names = (None if args.tpu_audit == "all"
                     else [n.strip() for n in args.tpu_audit.split(",")
                           if n.strip()])
            results = T.audit_all(names)
            data = T.save_baseline(results)
            print(f"tpu baseline: {len(data['configs'])} configs -> "
                  f"{T.BASELINE_PATH}", file=sys.stderr)
        return 0

    baseline = {} if args.no_baseline else L.load_baseline(args.baseline)
    new, old, stale = L.split_new(findings, baseline)

    report = {
        "findings": [f.to_json() for f in new],
        "baselined": len(old),
        "new": len(new),
        "stale_baseline_keys": stale,
        "rules": L.RULES,
    }
    failed = bool(new)

    if args.hlo_audit:
        # imported lazily: the pure lint path must not pull in jax
        from shadow_tpu.analysis import hlo_audit as H

        names = (sorted(H.CONTRACTS) if args.hlo_audit == "all"
                 else [n.strip() for n in args.hlo_audit.split(",") if
                       n.strip()])
        audit = H.audit_all(names)
        report["hlo_audit"] = audit
        for name, res in audit.items():
            if not res["ok"]:
                failed = True
                for v in res["violations"]:
                    print(f"hlo_audit: {v}", file=sys.stderr)

    if args.donation_audit:
        from shadow_tpu.analysis import donation as D

        don = D.audit_all()
        census = D.census_all()
        report["donation_audit"] = don
        report["transfer_census"] = census
        for name, res in don.items():
            if not res["ok"]:
                failed = True
                for v in res["violations"]:
                    print(f"donation_audit: {v}", file=sys.stderr)
        if not census["ok"]:
            failed = True
            for v in census["violations"]:
                print(f"transfer_census: {v}", file=sys.stderr)

    if args.mem_audit:
        from shadow_tpu.analysis import memory as M

        mem = M.audit_all()
        report["mem_audit"] = mem
        for name, res in mem.items():
            if not res["ok"]:
                failed = True
                for v in res["violations"]:
                    print(f"mem_audit: {v}", file=sys.stderr)

    if args.tpu_audit:
        from shadow_tpu.analysis import tpu_readiness as T

        names = (None if args.tpu_audit == "all"
                 else [n.strip() for n in args.tpu_audit.split(",")
                       if n.strip()])
        tpu = T.audit_all(names)
        report["tpu_readiness"] = tpu
        for name, res in tpu.items():
            if not res["ok"]:
                failed = True
                for v in res["violations"]:
                    print(f"tpu_audit: {v}", file=sys.stderr)

    for f in new:
        print(str(f), file=sys.stderr)
    if stale:
        print(f"note: {len(stale)} stale baseline keys (safe to "
              f"--update-baseline)", file=sys.stderr)
    print(f"shadowlint: {len(new)} new, {len(old)} baselined",
          file=sys.stderr)

    text = json.dumps(report, indent=1)
    if args.output:
        with open(args.output, "w", encoding="utf-8") as fh:
            fh.write(text + "\n")
    else:
        print(text)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
