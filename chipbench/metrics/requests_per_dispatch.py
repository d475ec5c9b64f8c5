"""Server layer (`serve/query_server.py`): requests answered per dispatched
group in the window, from `ServerStats.completed` and `.batches`."""


def read(w):
    if not w.counters.get("batches"):
        return None
    return w.counters["completed"] / w.counters["batches"]
