"""Cognitive-services pipeline composition: OCR -> sentiment -> custom API.

The reference's flagship notebook composition ("Cognitive Services -
Overview": chain several Azure AI calls over a DataFrame; SURVEY §3.5) as
one Table pipeline:

  1. OCR          — image bytes -> recognized text regions
  2. Lambda       — flatten OCR regions into a plain text column
  3. TextSentiment— text -> sentiment label
  4. SimpleHTTPTransformer — the same rows through a CUSTOM JSON service
     (the bring-your-own-endpoint escape hatch, SimpleHTTPTransformer.scala)

Everything runs against a local mock of the Azure wire protocol, so the
example is offline and deterministic; swap `url=` for real endpoints +
a real subscription key to run it against Azure.

Run: python examples/11_cognitive_pipeline.py
"""
import json
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.cognitive.text_analytics import TextSentiment
from mmlspark_tpu.cognitive.vision import OCR
from mmlspark_tpu.core.pipeline import LambdaTransformer, PipelineModel
from mmlspark_tpu.io.http.transformers import SimpleHTTPTransformer

# one fake "scanned document" per row: the mock OCR echoes these back as
# region/line/word structures, keyed by the image bytes
DOCS = {
    b"IMG-0": "the service was excellent and fast",
    b"IMG-1": "terrible delays ruined the whole trip",
    b"IMG-2": "an average experience nothing special",
}
NEGATIVE = {"terrible", "ruined", "delays"}


class _Mock(BaseHTTPRequestHandler):
    def do_POST(self):
        body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        if self.path.startswith("/vision/v2.0/ocr"):
            words = DOCS.get(bytes(body), "").split()
            out = {"language": "en", "regions": [{"lines": [
                {"words": [{"text": w} for w in words]}]}]}
        elif "/sentiment" in self.path:
            docs = json.loads(body)["documents"]
            out = {"documents": [
                {"id": d["id"],
                 "sentiment": ("negative" if NEGATIVE & set(d["text"].split())
                               else "positive")}
                for d in docs]}
        else:  # the custom service: uppercase + word count
            payload = json.loads(body)
            out = {"upper": payload["text"].upper(),
                   "words": len(payload["text"].split())}
        blob = json.dumps(out).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(blob)))
        self.end_headers()
        self.wfile.write(blob)

    def log_message(self, *a):
        pass


def ocr_text(row):
    """Flatten an OCR response into one string (the notebook's UDF)."""
    if row is None:
        return None
    return " ".join(
        w["text"]
        for region in row.get("regions", [])
        for line in region.get("lines", [])
        for w in line.get("words", []))


def main():
    srv = HTTPServer(("127.0.0.1", 0), _Mock)
    threading.Thread(target=srv.serve_forever, daemon=True,
                     name="example-mock-http").start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"

    imgs = np.empty(len(DOCS), dtype=object)
    for i, blob in enumerate(DOCS):
        imgs[i] = blob
    table = Table({"image": imgs})

    pipeline = PipelineModel([
        OCR(url=f"{base}/vision/v2.0/ocr", subscription_key="demo-key",
            image_bytes_col="image", output_col="ocr"),
        LambdaTransformer(lambda t: t.with_column(
            "text", np.asarray([ocr_text(r) for r in t["ocr"]],
                               dtype=object))),
        TextSentiment(url=f"{base}/text/analytics/v3.0/sentiment",
                      subscription_key="demo-key", text_col="text",
                      output_col="sentiment"),
        SimpleHTTPTransformer(url=f"{base}/custom/enrich",
                              input_cols=["text"], output_col="enriched"),
    ])
    out = pipeline.transform(table)

    for i in range(len(out)):
        sent = out["sentiment"][i]["sentiment"]
        enr = out["enriched"][i]
        print(f"doc{i}: text={out['text'][i]!r} sentiment={sent} "
              f"words={enr['words']}")
    sentiments = [out["sentiment"][i]["sentiment"] for i in range(len(out))]
    assert sentiments == ["positive", "negative", "positive"], sentiments
    assert all(out["enriched"][i]["upper"] == out["text"][i].upper()
               for i in range(len(out)))
    srv.shutdown()
    print("cognitive composition: OCR -> sentiment -> custom HTTP ok")


if __name__ == "__main__":
    main()
