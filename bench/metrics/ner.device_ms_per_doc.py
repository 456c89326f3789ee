"""Per document, the longest device time of the four NER services' calls
(the straggler the join waits for), averaged over the window's
documents, in ms."""


def read(run):
    per_doc = (run.trace or {}).get("ner_doc_device_s")
    if not per_doc:
        return None
    return 1e3 * sum(per_doc) / len(per_doc)
