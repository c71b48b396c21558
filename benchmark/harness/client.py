"""The client's side of ``HttpFrontend``: POST the statement, poll
``nextUri`` until the query ends, decode the result page. Copied from
``chip_smoke.py``'s served phase; a query's latency runs from the POST
to the decoded page."""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

TERMINAL = ("FINISHED", "FAILED")


def http_json(method: str, url: str, body: bytes | None = None,
              timeout: float = 600.0):
    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode("utf-8"))


def run_query(base: str, sql: str, poll_interval_s: float,
              timeout_s: float = 600.0) -> dict:
    """One query from the client's side. Never raises for a query that
    went wrong: the record says how it ended (``ok`` false on FAILED,
    an HTTP error, a timeout, or a page flagged ``approximate``)."""
    rec = {"ok": False, "id": None, "state": None, "error": None,
           "columns": None, "data": None, "polls": 0, "approximate": False}
    rec["t_submit"] = t0 = time.perf_counter()
    try:
        status, page = http_json("POST", base + "/v1/statement",
                                 sql.encode("utf-8"))
        rec["id"] = page.get("id")
        if status != 201:
            rec["error"] = f"POST returned {status}"
        while page["state"] not in TERMINAL:
            if time.perf_counter() - t0 > timeout_s:
                rec["error"] = f"no terminal page within {timeout_s} s"
                break
            time.sleep(poll_interval_s)
            _, page = http_json("GET", base + page.get(
                "nextUri", f"/v1/statement/{page['id']}"))
            rec["polls"] += 1
        rec["state"] = page["state"]
        rec["approximate"] = bool(page.get("approximate"))
        if page["state"] == "FINISHED":
            rec["columns"] = page["columns"]
            rec["data"] = page["data"]
        elif page["state"] == "FAILED":
            rec["error"] = f"{page.get('errorCode')}: {page.get('error')}"
    except (urllib.error.URLError, OSError, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["t_done"] = time.perf_counter()
    rec["latency_s"] = rec["t_done"] - t0
    rec["ok"] = (rec["state"] == "FINISHED" and rec["error"] is None
                 and not rec["approximate"])
    return rec


class Stream(threading.Thread):
    """One closed-loop client: the next query goes out when the last
    one's page is decoded; no new query starts after ``deadline``
    (a ``perf_counter`` time), the one in flight finishes and counts."""

    def __init__(self, index: int, base: str, order: list, sql_of, deadline,
                 poll_interval_s: float, on_done=None):
        super().__init__(name=f"bench-stream-{index}", daemon=True)
        self.index = index
        self.base = base
        self.order = order
        self.sql_of = sql_of
        self.deadline = deadline
        self.poll_interval_s = poll_interval_s
        self.on_done = on_done
        self.completions: list = []

    def run(self) -> None:
        i = 0
        while time.perf_counter() < self.deadline:
            template, bind = self.order[i % len(self.order)]
            rec = run_query(self.base, self.sql_of(template, bind),
                            self.poll_interval_s)
            rec.update(template=template, binding=bind, stream=self.index)
            if self.on_done is not None:
                self.on_done(rec)
            self.completions.append(rec)
            i += 1
