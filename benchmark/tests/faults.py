"""The timed path broken underneath the harness: engines that answer
wrong in the ways a cell can.  The tests drive a whole rehearsed run
over each and see `correct` come out false; `readings.py` reads the
same faults on the chip at a cell's own size."""
from __future__ import annotations

FAULTS = ("answer_altered", "half_the_rows", "key_altered")


def broken_engine(fault: str):
    from benchmark import engine as EN

    class Broken(EN.Engine):
        def register(self, tables):
            if fault == "half_the_rows":
                tables = dict(tables)
                li = tables["lineitem"]
                tables["lineitem"] = li.iloc[:len(li) // 2]
            super().register(tables)

        def run(self, query, *annotate):
            answer, clk = super().run(query, *annotate)
            if fault == "answer_altered":
                answer = answer.copy()
                col = [c for c in answer.columns
                       if str(answer[c].dtype).lower().startswith("float")][-1]
                answer[col] = answer[col] * (1.0 + 1e-3)
            if fault == "key_altered" and len(answer.columns) > 1:
                answer = answer.copy()
                first = answer.columns[0]
                answer[first] = answer[first].iloc[::-1].to_numpy()
            return answer, clk
    return Broken
