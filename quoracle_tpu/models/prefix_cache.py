"""Radix prefix cache: ref-counted, copy-on-write KV page sharing across
sessions (vLLM automatic-prefix-caching / SGLang RadixAttention analog,
re-derived for the paged SessionStore in models/generate.py).

Every consensus round fans the same built prompt out to K rows, and every
child agent inherits most of its parent's system/task preamble — so the
same page-aligned token blocks get prefilled over and over. This module
maps token prefixes to the pages that already hold their KV:

  * a RADIX TREE over PAGE-ALIGNED token blocks: each node is exactly one
    page of the device pool, its edge labeled with that page's ``page``
    token ids; a root-to-node path spells a cached token prefix whose KV
    is resident in the path's pages;
  * the tree holds its OWN REFERENCE on every node's page (the store's
    refcount dict), so cached prefixes survive the death of the session
    that prefilled them — the old donor-scan sharing only worked while
    the donor stayed resident;
  * LRU EVICTION strips unreferenced leaves (pages whose ONLY remaining
    reference is the tree's) when the pool runs dry — shared live pages
    are never evicted, and eviction is leaf-first so an evicted node can
    never orphan cached descendants;
  * COPY-ON-WRITE is enforced at the write site (generate._run_paged):
    a session about to rewrite a shared page beyond its identical-prefix
    region — including the partially-filled boundary page it is
    extending — swaps in a fresh page and leaves the shared copy (and
    therefore every tree/adopter reader) untouched. The engine reports
    those swaps here (``note_cow``) so the counter sits with the rest of
    the cache telemetry.

Invariants (asserted by tests/test_prefix_cache.py):
  I1  a page is freed only when its refcount reaches zero — never while a
      session, an in-flight batch, or the tree still references it;
  I2  tree page content is immutable: writers either rewrite a shared page
      byte-identically (the gather scatter inside the identical-prefix
      region) or COW-swap it — a cached block's KV never changes under a
      reader; nor does what rides the page beside its rows (a model with
      conv layers keeps the layers' state at the page's end under the same
      page id, GenerateEngine._ensure_pool: a full page is never written
      again, so its record stands, and a COW swap leaves it with the
      donor; tests/test_shortconv_moe.py);
  I3  sessions hold contiguous root-path references, so iterative
      unreferenced-LEAF eviction reaches exactly the reclaimable nodes.

A model with a WINDOW group of attention layers beside the full one
(config.kv_groups; the store's ``window`` ids) keeps a cached block in both:
a node holds the block's full-group page and, as ``wpage``, its window-group
page, with the tree's own reference on each, so a new session can adopt a
prefix at ANY page boundary — the full group's pages whole and the window
group's for the last window (SessionStore.match_prefix). When the window
group runs out of pages the tree lets go of window pages nobody else reads,
least recently matched first (``strip_window``): the node stays, with its
full-group page, and a match ends at the longest boundary whose last window
is still cached (``_walk``) — the hot shared prompt is touched at every
match and keeps its pages, a finished conversation's blocks lose theirs
first. A session holding a node's window page holds its full-group page
too, so leaf eviction's rule (no reference but the tree's on the full-group
page) covers both.

A model with ssm layers (the store's ``records``) holds megabytes of
recurrent state a session, so a node does NOT carry the state at its end as
a matter of course: a node MAY hold a SNAPSHOT (``record``, an id of the
store's record pool, owned by the tree), taken by the engine at a boundary
it chose — the end of a cached prefix that a new session matched and found
no snapshot at (generate.py ``_run_paged``). A match is cut back to the
deepest node that holds one (SessionStore.match_prefix) and the rest is
prefilled again. I2 covers a snapshot too: it is written once, by the tick
that took it, and only ever read after — an adopting row's chunk forward
reads it and writes the row's OWN record (the copy that adoption is), and a
tick holds a second reference while it reads, so ``strip_records`` (the
record pool's pressure valve: least recently matched first, the node keeps
its page) never frees a record under a reader. A node that goes
(``evict``, ``clear``) gives its snapshot back.

Locking: all mutating/inspecting methods assume the owning SessionStore's
RLock is held (the store re-enters it freely); the store's public wrappers
(`match_prefix`, `insert_prefix`, `alloc`) take it.
"""

from __future__ import annotations

import time
from collections import Counter
from typing import Optional, Sequence


class _Node:
    """One cached page: edge label ``block`` (page-length token tuple,
    relative to the parent path), pool page id, LRU stamp."""

    __slots__ = ("block", "page", "wpage", "record", "children", "parent",
                 "last_used")

    def __init__(self, block: tuple, page: int, parent: "Optional[_Node]"):
        self.block = block
        self.page = page
        self.wpage = 0      # its page in the store's window group, if any
        self.record = 0     # a snapshot of the ssm state at its end, if any
        self.children: dict[tuple, _Node] = {}
        self.parent = parent
        self.last_used = time.monotonic()


class RadixPrefixCache:
    """Radix tree over page-aligned KV blocks of one SessionStore's pool."""

    def __init__(self, store):
        self.store = store
        self.page = store.page
        self._root = _Node((), 0, None)      # sentinel; page 0 is scratch
        self._pages: dict[int, _Node] = {}   # page id -> its node
        self._wpages: dict[int, _Node] = {}  # window-group page id -> node
        # counters (monotonic; exposed via stats() -> web API + bench)
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.miss_tokens = 0
        self.inserted_pages = 0
        self.evicted_pages = 0
        self.cow_copies = 0
        self.stripped_window_pages = 0

    # -- lookup ------------------------------------------------------------

    def _walk(self, tokens: Sequence[int], max_reuse: int) -> list[_Node]:
        """The node path for the longest cached page-aligned prefix of
        ``tokens``, bounded by ``max_reuse`` (callers pass len-1 so >= 1
        suffix token always re-runs to produce last-position logits)."""
        page = self.page
        node = self._root
        path: list[_Node] = []
        n_blocks = min(len(tokens), max_reuse) // page
        for j in range(n_blocks):
            block = tuple(tokens[j * page:(j + 1) * page])
            child = node.children.get(block)
            if child is None:
                break
            path.append(child)
            node = child
        w = self.store.window
        if w is not None:
            # a match can end only where the window group's pages that a
            # query behind it reaches are all cached
            while path and not all(n.wpage for n in path[
                    w.first_page(len(path) * page, page):]):
                path.pop()
        return path

    def match(self, tokens: Sequence[int],
              max_reuse: int) -> tuple[list[int], int]:
        """Longest cached page-aligned prefix: (pages, n_tokens). Bumps the
        path's LRU stamps and the hit/miss counters — call once per real
        lookup (the wave planner probes via match_len instead)."""
        path = self._walk(tokens, max_reuse)
        now = time.monotonic()
        for node in path:
            node.last_used = now
        matched = len(path) * self.page
        if path:
            self.hits += 1
            self.hit_tokens += matched
        else:
            self.misses += 1
            self.miss_tokens += len(tokens)
        return [n.page for n in path], matched

    def match_len(self, tokens: Sequence[int], max_reuse: int) -> int:
        """Counter-free probe (intra-batch wave planning)."""
        return len(self._walk(tokens, max_reuse)) * self.page

    # -- insert ------------------------------------------------------------

    def insert(self, tokens: Sequence[int], pages: Sequence[int],
               wpages: Optional[Sequence[int]] = None) -> int:
        """Record a prefilled prefix: every FULL page of ``tokens`` whose
        block is not yet cached gets a node holding ``pages[j]`` and a tree
        reference on it. Blocks already cached keep their existing node
        (dedupe — the caller's duplicate page stays the session's own).
        ``wpages`` (a store with a window group): the caller's window-group
        pages for the same blocks, 0 where it holds none; a node that has
        no window page — new, or stripped since — takes the caller's, with
        a tree reference of its own. Returns the number of new nodes."""
        page = self.page
        node = self._root
        added = 0
        for j in range(len(tokens) // page):
            pg = pages[j] if j < len(pages) else None
            if pg is None:
                break
            block = tuple(tokens[j * page:(j + 1) * page])
            child = node.children.get(block)
            if child is None:
                if pg in self._pages or pg == 0:
                    break      # page already cached under another path
                child = _Node(block, pg, node)
                node.children[block] = child
                self._pages[pg] = child
                # the tree's own reference: absent refcount key == 1
                self.store._refs[pg] = self.store._refs.get(pg, 1) + 1
                added += 1
            wp = wpages[j] if wpages is not None and j < len(wpages) else 0
            if wp and not child.wpage and wp not in self._wpages:
                child.wpage = wp
                self._wpages[wp] = child
                self.store.window.acquire([wp])
            child.last_used = time.monotonic()
            node = child
        self.inserted_pages += added
        return added

    # -- eviction ----------------------------------------------------------

    def _evictable_leaf(self) -> Optional[_Node]:
        """LRU leaf whose page's ONLY remaining reference is the tree's.
        Refcount semantics (store._refs, absent key == 1): the count is the
        number of current holders — the allocating session's base ref, one
        per adopter acquire, one for the tree. A session dropping its pages
        decrements normally, so a page cached here but referenced by nobody
        else sits at exactly 1."""
        best: Optional[_Node] = None
        stack = [self._root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node is self._root or node.children:
                continue
            if self.store._refs.get(node.page, 1) != 1 or (
                    node.wpage
                    and self.store.window._refs.get(node.wpage, 1) != 1):
                continue       # a session/adopter still reads it
            if best is None or node.last_used < best.last_used:
                best = node
        return best

    def _remove(self, node: _Node) -> None:
        del node.parent.children[node.block]
        self._pages.pop(node.page, None)
        self._drop_window(node)
        self._drop_record(node)

    # -- snapshots of recurrent state (a store with ``records``) ------------

    def _drop_record(self, node: _Node) -> int:
        """Give up a node's snapshot; returns 1 where that freed it."""
        if not node.record:
            return 0
        rec, node.record = node.record, 0
        return self.store.records.release([rec])

    def records_of(self, pages: Sequence[int]) -> list[int]:
        """The snapshots (0: none) of the nodes that hold ``pages``."""
        return [self._pages[p].record for p in pages]

    def attach_record(self, tokens: Sequence[int], rec: int) -> bool:
        """Hand the tree a snapshot of the state after ``tokens`` (a whole
        number of pages): the node at that depth takes it, unless it has
        one or is gone — then the caller keeps (and releases) it."""
        path = self._walk(tokens, len(tokens))
        if len(path) * self.page != len(tokens) or path[-1].record:
            return False
        path[-1].record = rec
        return True

    def _idle_snapshots(self) -> list:
        refs = self.store.records._refs
        return [n for n in self._pages.values()
                if n.record and refs.get(n.record, 1) == 1]

    def idle_records(self) -> int:
        """Snapshots no tick is reading: what ``strip_records`` can free."""
        return len(self._idle_snapshots())

    def strip_records(self, n: int) -> int:
        """Free up to ``n`` snapshots that only the tree references, least
        recently matched nodes first; the nodes stay, with their pages."""
        idle = sorted(self._idle_snapshots(),
                      key=lambda node: node.last_used)[:n]
        return sum(self._drop_record(node) for node in idle)

    def _drop_window(self, node: _Node) -> int:
        """Give up the tree's reference on a node's window-group page;
        returns 1 where that freed it."""
        if not node.wpage:
            return 0
        wp, node.wpage = node.wpage, 0
        del self._wpages[wp]
        return self.store.window.release([wp])

    def strip_window(self, n: int) -> int:
        """Free up to ``n`` WINDOW-group pages that only the tree still
        references, least recently matched nodes first; the nodes stay,
        with their full-group pages (module docstring). Returns the pages
        freed."""
        refs = self.store.window._refs
        idle = sorted((node for wp, node in self._wpages.items()
                       if refs.get(wp, 1) == 1),
                      key=lambda node: node.last_used)[:n]
        freed = sum(self._drop_window(node) for node in idle)
        self.stripped_window_pages += freed
        return freed

    def window_pages(self) -> set:
        """Every window-group page the tree holds a reference on."""
        return set(self._wpages)

    def window_pages_of(self, pages: Sequence[int]) -> list[int]:
        """The window-group pages of the nodes that hold ``pages``."""
        return [self._pages[p].wpage for p in pages]

    def _node_tokens(self, node: _Node) -> list:
        """The full token prefix a node's page caches (root-path blocks
        concatenated) — the tier's content-addressed key."""
        blocks = []
        while node is not self._root:
            blocks.append(node.block)
            node = node.parent
        out: list = []
        for b in reversed(blocks):
            out.extend(b)
        return out

    def evict(self, n: int) -> int:
        """Free up to ``n`` pages by stripping unreferenced LRU leaves.
        Returns pages actually freed to the store's free list. With a
        tier attached (serving/kvtier.py), a stripped leaf's block is
        CAPTURED host-side first — eviction demotes instead of
        destroying, and a later lookup pages the block back in."""
        freed = 0
        while freed < n:
            leaf = self._evictable_leaf()
            if leaf is None:
                break
            if self.store.tier is not None:
                self.store.tier.capture_leaf(self._node_tokens(leaf),
                                             leaf.page)
            self._remove(leaf)
            self.store._release([leaf.page])   # last ref -> free list
            freed += 1
            self.evicted_pages += 1
        return freed

    def clear(self) -> int:
        """Drop every node, releasing the tree's references (pages still
        held by sessions survive with refcount decremented)."""
        dropped = 0
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            self.store._release([node.page])
            self._drop_window(node)
            self._drop_record(node)
            dropped += 1
        self._root.children.clear()
        self._pages.clear()
        return dropped

    # -- alloc accounting --------------------------------------------------

    def holds(self, page: int) -> bool:
        return page in self._pages

    def evictable_after(self, released: Counter) -> int:
        """How many tree pages would FREE if ``released`` (page -> count of
        references victim sessions would give up) were applied and the tree
        then stripped leaves bottom-up. Exact simulation for
        SessionStore.alloc's attainability check: a node frees iff its
        whole subtree frees and no reference beyond the tree's survives."""
        def strippable(node: _Node) -> tuple[bool, int]:
            count = 0
            all_ok = True
            for child in node.children.values():
                ok, c = strippable(child)
                count += c
                all_ok = all_ok and ok
            if node is self._root:
                return True, count
            remaining = self.store._refs.get(node.page, 1) \
                - released.get(node.page, 0)
            ok = all_ok and remaining <= 1     # only the tree's ref left
            return ok, count + (1 if ok else 0)

        return strippable(self._root)[1]

    # -- telemetry ---------------------------------------------------------

    def note_cow(self, n: int = 1) -> None:
        """The engine swapped ``n`` shared pages for fresh copies before a
        divergent write (generate._run_paged shared_beyond/boundary swap)."""
        self.cow_copies += n

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_tokens": self.hit_tokens,
            "miss_tokens": self.miss_tokens,
            "inserted_pages": self.inserted_pages,
            "evicted_pages": self.evicted_pages,
            "cow_copies": self.cow_copies,
            "cached_pages": len(self._pages),
            # a store with a window group: that group's pages held, and
            # those given back under its pressure
            "cached_window_pages": len(self._wpages),
            "stripped_window_pages": self.stripped_window_pages,
            # a store with a record pool: snapshots the tree holds
            "cached_records": sum(1 for n in self._pages.values()
                                  if n.record),
        }

    def occupancy(self) -> dict:
        """Live occupancy — the point-in-time complement to the monotonic
        stats() counters (ISSUE 3 satellite): resident pages (every tree
        node), REFERENCED pages (refcount > 1 — a session or in-flight
        adopter reads them beyond the tree's own reference, so eviction
        cannot touch them), and evictable LEAF pages (refcount exactly 1
        and no children — what one evict() pass could reclaim right now).
        Assumes the owning SessionStore's lock is held, like every other
        inspecting method here."""
        referenced = evictable = 0
        for pg, node in self._pages.items():
            if self.store._refs.get(pg, 1) > 1:
                referenced += 1
            elif not node.children:
                evictable += 1
        return {
            "resident_pages": len(self._pages),
            "referenced_pages": referenced,
            "evictable_leaf_pages": evictable,
        }

    def __len__(self) -> int:
        return len(self._pages)
