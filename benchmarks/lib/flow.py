"""The window job's flow report (`FusedJob.flow_report()`), as the program
leaves it on the `rw:commit.gauges` span of every checkpoint: per node
`node`, `i`, `kind`, `rows_in` / `rows_out` (live rows, summed over the
job's epochs), `lanes` (the rows-wide shape the step was handed an epoch),
for keyed nodes `live` and `capacity`, for the join `need_pairs` and
`pairs`. A program that leaves none (a commit before the report) reads as
`None`."""
import spans


def report():
    """The report of the window's last checkpoint, or `None`."""
    p = spans.load()
    if p is None:
        return None
    found = [s["flow_report"] for s in p.of(p.window, "rw:commit.gauges")
             if s.get("flow_report")]
    return found[-1] if found else None


def nodes(kind=None):
    """The last report's node entries, all or those of one `kind`; `[]`
    where there is no report."""
    rep = report()
    if not rep:
        return []
    return [n for n in rep["nodes"] if kind is None or n["kind"] == kind]
