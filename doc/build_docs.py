#!/usr/bin/env python
"""Zero-dependency docs builder: doc/*.md + API autodoc -> doc/html/.

The reference ships a Sphinx + autodoc + ReadTheDocs build
(reference: doc/conf.py, .readthedocs.yaml:1-20).  This repo ships the
same Sphinx entry points (doc/conf.py here consumes the markdown via
MyST when Sphinx is available) *plus* this stdlib-only fallback so
``make docs`` produces HTML in any environment — including CI images
where Sphinx cannot be installed.  Sphinx output is preferred when
importable; the fallback renders the same sources.

Markdown subset: ATX headers, fenced code, ordered/unordered lists,
tables, blockquotes, inline code/bold/italic/links — the subset doc/*.md
actually uses (checked by tests/test_docs.py).
"""

from __future__ import annotations

import html
import inspect
import re
import sys
from pathlib import Path

DOC = Path(__file__).resolve().parent
OUT = DOC / "html"
PAGES = ["index", "basic_usage", "examples", "parallelism", "layer_spec",
         "serving",
         "compression", "fusion", "algorithms", "schedule_ir", "overlap",
         "resilience", "reshard", "elasticity", "transport", "analysis",
         "observability", "self_tuning", "api_reference",
         "design_tpu", "glossary"]

CSS = """
body { font-family: -apple-system, "Segoe UI", Roboto, sans-serif;
       max-width: 56rem; margin: 2rem auto; padding: 0 1rem;
       line-height: 1.55; color: #1a1a2e; }
nav { border-bottom: 1px solid #ddd; padding-bottom: .6rem;
      margin-bottom: 1.2rem; }
nav a { margin-right: .9rem; text-decoration: none; color: #0b5cad; }
pre { background: #f6f8fa; padding: .8rem; overflow-x: auto;
      border-radius: 6px; }
code { background: #f6f8fa; padding: .1rem .25rem; border-radius: 4px;
       font-size: .92em; }
pre code { padding: 0; }
table { border-collapse: collapse; }
td, th { border: 1px solid #ccc; padding: .3rem .6rem; }
h1, h2, h3 { line-height: 1.25; }
blockquote { border-left: 4px solid #ccc; margin-left: 0;
             padding-left: 1rem; color: #444; }
.api-entry { margin: 1.2rem 0; padding: .8rem; border: 1px solid #e2e2e8;
             border-radius: 6px; }
.api-sig { font-family: ui-monospace, monospace; font-weight: 600; }
.api-doc { white-space: pre-wrap; font-size: .95em; margin-top: .5rem; }
"""


def _inline(text: str) -> str:
    text = html.escape(text, quote=False)
    text = re.sub(r"`([^`]+)`", r"<code>\1</code>", text)
    text = re.sub(r"\*\*([^*]+)\*\*", r"<strong>\1</strong>", text)
    text = re.sub(r"(?<!\*)\*([^*\s][^*]*)\*(?!\*)", r"<em>\1</em>", text)
    text = re.sub(r"\[([^\]]+)\]\(([^)\s]+)\)",
                  lambda m: f'<a href="{m.group(2)}">{m.group(1)}</a>', text)
    return text


def md_to_html(src: str) -> str:
    out, i, lines = [], 0, src.splitlines()
    list_stack: list[str] = []

    def close_lists():
        while list_stack:
            out.append(f"</{list_stack.pop()}>")

    while i < len(lines):
        line = lines[i]
        if line.startswith("```"):
            close_lists()
            block = []
            i += 1
            while i < len(lines) and not lines[i].startswith("```"):
                block.append(lines[i])
                i += 1
            out.append("<pre><code>"
                       + html.escape("\n".join(block)) + "</code></pre>")
            i += 1
            continue
        m = re.match(r"^(#{1,6})\s+(.*)$", line)
        if m:
            close_lists()
            n = len(m.group(1))
            out.append(f"<h{n}>{_inline(m.group(2))}</h{n}>")
            i += 1
            continue
        if re.match(r"^\s*\|.*\|\s*$", line):
            close_lists()
            rows = []
            while i < len(lines) and re.match(r"^\s*\|.*\|\s*$", lines[i]):
                cells = [c.strip() for c in lines[i].strip().strip("|")
                         .split("|")]
                if not all(re.fullmatch(r":?-+:?", c) for c in cells):
                    rows.append(cells)
                i += 1
            out.append("<table>")
            for r, cells in enumerate(rows):
                tag = "th" if r == 0 else "td"
                out.append("<tr>" + "".join(
                    f"<{tag}>{_inline(c)}</{tag}>" for c in cells) + "</tr>")
            out.append("</table>")
            continue
        m = re.match(r"^(\s*)([-*]|\d+\.)\s+(.*)$", line)
        if m:
            kind = "ol" if m.group(2)[0].isdigit() else "ul"
            if not list_stack or list_stack[-1] != kind:
                close_lists()
                out.append(f"<{kind}>")
                list_stack.append(kind)
            out.append(f"<li>{_inline(m.group(3))}</li>")
            i += 1
            continue
        if line.startswith("> "):
            close_lists()
            out.append(f"<blockquote>{_inline(line[2:])}</blockquote>")
            i += 1
            continue
        if not line.strip():
            close_lists()
            i += 1
            continue
        close_lists()
        para = [line]
        while (i + 1 < len(lines) and lines[i + 1].strip()
               and not re.match(r"^(#|```|\s*[-*]\s|\s*\d+\.\s|\||> )",
                                lines[i + 1])):
            i += 1
            para.append(lines[i])
        out.append(f"<p>{_inline(' '.join(para))}</p>")
        i += 1
    close_lists()
    return "\n".join(out)


def page(title: str, body: str) -> str:
    nav = " ".join(
        f'<a href="{p}.html">{p.replace("_", " ")}</a>' for p in PAGES
    ) + ' <a href="api_autodoc.html">api autodoc</a>'
    return (f"<!doctype html><html><head><meta charset='utf-8'>"
            f"<title>{html.escape(title)} — mpi4torch_tpu</title>"
            f"<style>{CSS}</style></head><body>"
            f"<nav>{nav}</nav>{body}</body></html>")


def autodoc_html() -> str:
    """Introspected API reference — the autodoc analogue (reference:
    doc/conf.py autodoc extension + api_reference.rst automethod
    directives)."""
    sys.path.insert(0, str(DOC.parent))   # build from a source checkout
    import mpi4torch_tpu as mpi
    from mpi4torch_tpu import ops as mpi_ops

    sections = []

    def entry(obj, name):
        try:
            sig = name + str(inspect.signature(obj))
        except (TypeError, ValueError):
            sig = name
        doc = inspect.getdoc(obj) or "(no docstring)"
        return (f'<div class="api-entry"><div class="api-sig">'
                f"{html.escape(sig)}</div>"
                f'<div class="api-doc">{html.escape(doc)}</div></div>')

    sections.append("<h1>API autodoc</h1>"
                    "<p>Generated from live signatures and docstrings "
                    "(the reference builds this with Sphinx autodoc, "
                    "doc/conf.py).</p>")

    sections.append("<h2>mpi4torch_tpu (facade)</h2>")
    for name in sorted(mpi.__all__):
        obj = getattr(mpi, name)
        if inspect.isclass(obj):
            sections.append(entry(obj, name))
            for mname, meth in sorted(vars(obj).items()):
                if mname.startswith("_") or not callable(meth):
                    continue
                sections.append(entry(meth, f"{name}.{mname}"))
        elif callable(obj):
            sections.append(entry(obj, name))
        else:
            sections.append(
                f'<div class="api-entry"><div class="api-sig">'
                f"{html.escape(name)}</div>"
                f'<div class="api-doc">{html.escape(repr(obj))}</div></div>')

    sections.append("<h2>mpi4torch_tpu.ops</h2>")
    for name in sorted(mpi_ops.__all__):
        sections.append(entry(getattr(mpi_ops, name), name))
    return "\n".join(sections)


def main() -> int:
    OUT.mkdir(parents=True, exist_ok=True)
    for p in PAGES:
        src = (DOC / f"{p}.md").read_text()
        title = p.replace("_", " ")
        (OUT / f"{p}.html").write_text(page(title, md_to_html(src)))
    (OUT / "api_autodoc.html").write_text(page("API autodoc",
                                               autodoc_html()))
    n = len(list(OUT.glob("*.html")))
    print(f"built {n} pages -> {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
