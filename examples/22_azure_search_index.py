"""Azure Search indexing: pushing a table of artworks to a search index.

Reference workload: "AzureSearchIndex - Met Artworks.ipynb" — define an
index schema, write every DataFrame row as a search document in batches
with retry/bisection on throttling (cognitive AzureSearchWriter.scala /
AzureSearchAPI.scala createIndexIfNotExists + push with backoff).

Zero-egress stand-in for the service: a loopback HTTP mock that speaks
the two endpoints the writer uses (PUT /indexes/{name}, POST
/indexes/{name}/docs/index) and throttles the FIRST attempt of one
batch with a 503 — demonstrating the exponential-backoff retry exactly
where the real service would push back.

Run: python examples/22_azure_search_index.py
"""
import json
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from mmlspark_tpu import Table
from mmlspark_tpu.cognitive import AzureSearchWriter

ARTWORKS = [
    ("1", "The Great Wave", "Hokusai", "Japanese woodblock print"),
    ("2", "Bridge Over a Pond", "Monet", "French impressionist painting"),
    ("3", "Bronze Cat", "Unknown", "Egyptian votive sculpture"),
    ("4", "Red-figure Amphora", "Euphronios", "Greek vase painting"),
    ("5", "Self-Portrait", "Rembrandt", "Dutch golden age painting"),
    ("6", "Jade Mask", "Unknown", "Maya funerary mask"),
    ("7", "Starry Night Study", "After van Gogh", "post-impressionist"),
]


class _MockSearch(BaseHTTPRequestHandler):
    indexes: dict = {}
    docs: list = []
    throttled_once = {"done": False}

    def _reply(self, code, body=b"{}"):
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_PUT(self):
        body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        name = self.path.split("/indexes/")[1].split("?")[0]
        _MockSearch.indexes[name] = json.loads(body)
        self._reply(201)

    def do_POST(self):
        body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        docs = json.loads(body)["value"]
        if not _MockSearch.throttled_once["done"]:
            # throttle the first push: the writer must back off and retry
            _MockSearch.throttled_once["done"] = True
            self._reply(503)
            return
        _MockSearch.docs.extend(docs)
        self._reply(200, json.dumps(
            {"value": [{"key": d.get("id"), "status": True}
                       for d in docs]}).encode())

    def log_message(self, *a):  # quiet
        pass


def main():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _MockSearch)
    threading.Thread(target=srv.serve_forever, daemon=True,
                     name="example-mock-search").start()
    base = f"http://127.0.0.1:{srv.server_port}"

    ids, titles, artists, descs = (list(c) for c in zip(*ARTWORKS))
    table = Table({"id": ids, "title": titles, "artist": artists,
                   "description": descs})
    writer = AzureSearchWriter(
        index_name="met-artworks", key="demo-key",
        index_definition={"name": "met-artworks", "fields": [
            {"name": "id", "type": "Edm.String", "key": True},
            {"name": "title", "type": "Edm.String"},
            {"name": "artist", "type": "Edm.String"},
            {"name": "description", "type": "Edm.String"},
        ]},
        batch_size=3, base_url=base,
    )
    written = writer.write(table)
    srv.shutdown()

    print(f"index created: {list(_MockSearch.indexes)} "
          f"({len(_MockSearch.indexes['met-artworks']['fields'])} fields)")
    print(f"documents written: {written} in batches of <=3 "
          f"(first batch 503-throttled, retried with backoff)")
    assert written == len(ARTWORKS)
    assert len(_MockSearch.docs) == len(ARTWORKS)
    assert all(d["@search.action"] == "upload" for d in _MockSearch.docs)
    sample = next(d for d in _MockSearch.docs if d["id"] == "4")
    print(f"sample doc: {sample['title']!r} by {sample['artist']}")


if __name__ == "__main__":
    main()
